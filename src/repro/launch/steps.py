"""Step functions: train_step (fwd+bwd+AdamW), prefill_step, serve_step.

These are THE functions the dry-run lowers and the trainer/server jit.
"""

from __future__ import annotations


import contextlib

import jax
import jax.numpy as jnp

from repro.optim import adamw_update, clip_by_global_norm, cosine_schedule


def make_train_step(api, *, base_lr=1e-3, weight_decay=0.01, total_steps=100_000,
                    warmup_steps=1000, max_grad_norm=1.0, mesh_info=None):
    """(params, opt_state, batch) → (params, opt_state, metrics).

    ``mesh_info`` — an optional ``(mesh, axis)`` pair.  When given, the loss
    (and its backward) is traced inside :func:`mesh_context`, so a
    ``"sharded"`` backend resolves the mesh even when the step is jitted
    from a scope that no longer holds the context (trainers capture the
    mesh once at build time, same as ``ServingEngine``)."""

    def _scope():
        if mesh_info is None:
            return contextlib.nullcontext()
        from repro.distributed import mesh_context
        return mesh_context(mesh_info[0], axis=mesh_info[1])

    def train_step(params, opt_state, batch):
        with _scope():
            (loss, metrics), grads = jax.value_and_grad(
                api.loss, has_aux=True)(params, batch)
        with jax.named_scope("clip"):
            grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        with jax.named_scope("optimizer"):
            lr = cosine_schedule(opt_state["step"], base_lr=base_lr,
                                 total_steps=total_steps,
                                 warmup_steps=warmup_steps)
            params, opt_state = adamw_update(params, grads, opt_state, lr=lr,
                                             weight_decay=weight_decay)
        out = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        out.update({k: v for k, v in metrics.items() if v.ndim == 0})
        return params, opt_state, out

    return train_step


def make_prefill_step(api):
    """Forward pass returning LAST-position logits (B, V) — lowering the full
    (B, N, V) logits tensor would dominate memory for 200k vocabs."""

    def prefill_step(params, batch):
        out = api.forward(params, batch)
        return out[:, -1].astype(jnp.float32)

    return prefill_step


def make_serve_step(api, *, greedy: bool = True):
    """(params, caches, token (B,)) → (next_token (B,), logits (B,V), caches)."""

    def serve_step(params, caches, token):
        logits, caches = api.decode_step(params, token, caches)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, caches

    return serve_step


def make_paged_serve_step(api, *, page: int):
    """The continuous-batching decode step (block-table addressing).

    (params, caches, token (B,), table (B, n_pages) int32, lengths (B,
    int32)) → (next_token (B,), logits (B, V), caches).  ``page`` is static
    (baked into the jit); the tiny table/lengths arrays are pushed from the
    host scheduler each call, so ONE compiled step serves every admission /
    retirement configuration."""

    def paged_serve_step(params, caches, token, table, lengths):
        logits, caches = api.paged_decode_step(params, token, caches, table,
                                               lengths, page)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, caches

    return paged_serve_step


def make_paged_serve_window(api, *, page: int):
    """W greedy continuous-batching steps in ONE compiled call (lax.scan).

    Between host scheduling events (admission, retirement) a greedy
    schedule is VALUE-independent, so the engine batches W decode steps per
    dispatch instead of paying host round-trip latency per token.  Per-step
    feeds are data: ``feed (W, B)`` holds prompt tokens and ``use_prev
    (W, B)`` flips a slot to self-feeding (its previous sample) once its
    prompt is exhausted — the prefill→decode transition happens mid-window
    with no host involvement.  ``occ (B,) int32`` advances only occupied
    slots' lengths; W is baked into the compiled shape (the engine
    quantizes it to powers of two so at most log₂(W_max)+1 variants ever
    compile).

    (params, caches, feed (W, B) int32, use_prev (W, B) bool, prev (B,)
    int32, table (B, n_pages) int32, lengths (B,) int32, occ (B,) int32)
    → (samples (W, B) int32, caches)."""

    def paged_serve_window(params, caches, feed, use_prev, prev, table,
                           lengths, occ):
        def body(carry, xs):
            caches, prev, lengths = carry
            feed_t, use_t = xs
            tok = jnp.where(use_t, prev, feed_t)
            logits, caches = api.paged_decode_step(params, tok, caches,
                                                   table, lengths, page)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (caches, nxt, lengths + occ), nxt

        (caches, _, _), samples = jax.lax.scan(
            body, (caches, prev, lengths), (feed, use_prev))
        return samples, caches

    return paged_serve_window
