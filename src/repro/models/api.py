"""Family-dispatch API: one uniform interface over all model families.

Used by the trainer, the serving engine, the dry-run and the smoke tests:

    api = model_api(mcfg)
    params = api.init(key)
    loss, metrics = api.loss(params, batch)          # train step core
    logits = api.forward(params, batch)              # prefill
    caches = api.cache_init(batch_size, max_len)     # decode state
    logits, caches = api.decode_step(params, token, caches)
    batch = api.make_batch(rng, B, N)                # real arrays (tests)
    specs = api.batch_specs(B, N)                    # ShapeDtypeStructs (dry-run)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import encdec as _ed
from repro.models import pointcloud as _pc
from repro.models import transformer as _tf
from repro.models import vlm as _vlm


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    mcfg: Any
    init: Callable
    loss: Callable
    forward: Callable
    make_batch: Callable
    batch_specs: Callable
    cache_init: Callable | None = None
    cache_specs: Callable | None = None
    decode_step: Callable | None = None
    # paged continuous-batching decode (LM families; serving/paged_cache.py
    # owns the host-side tables these consume)
    paged_cache_init: Callable | None = None
    paged_decode_step: Callable | None = None
    cache_reset_slot: Callable | None = None
    cache_copy_block: Callable | None = None
    has_recurrent_state: bool = False
    # forward that also returns per-layer selected block ids and replays
    # batch["select"] when present (BSA point clouds; models.pointcloud)
    forward_selection: Callable | None = None

    @property
    def has_decoder(self) -> bool:
        return self.decode_step is not None

    @property
    def has_paged_decoder(self) -> bool:
        return self.paged_decode_step is not None


def _lm_api(mcfg) -> ModelAPI:
    def make_batch(rng, B, N):
        toks = rng.integers(0, mcfg.vocab_size, (B, N), dtype=np.int32)
        return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}

    def batch_specs(B, N):
        t = jax.ShapeDtypeStruct((B, N), jnp.int32)
        return {"tokens": t, "labels": t}

    def cache_init(B, S, dtype=jnp.bfloat16):
        return _tf.lm_cache_init(mcfg, B, S, dtype)

    def cache_specs(B, S, dtype=jnp.bfloat16):
        return jax.eval_shape(lambda: cache_init(B, S, dtype))

    return ModelAPI(
        mcfg=mcfg,
        init=lambda key: _tf.lm_init(key, mcfg),
        loss=lambda p, b: _tf.lm_loss(p, b, mcfg=mcfg),
        forward=lambda p, b: _tf.lm_apply(p, b["tokens"], mcfg=mcfg)[0],
        make_batch=make_batch,
        batch_specs=batch_specs,
        cache_init=cache_init,
        cache_specs=cache_specs,
        decode_step=lambda p, tok, c: _tf.lm_decode_step(p, tok, c, mcfg=mcfg),
        paged_cache_init=lambda B, num_blocks, page, dtype=jnp.bfloat16:
            _tf.lm_paged_cache_init(mcfg, B, num_blocks, page, dtype),
        paged_decode_step=lambda p, tok, c, table, lengths, page:
            _tf.lm_paged_decode_step(p, tok, c, table, lengths, mcfg=mcfg,
                                     page=page),
        cache_reset_slot=lambda c, slot: _tf.lm_paged_cache_reset_slot(
            mcfg, c, slot),
        cache_copy_block=lambda c, src, dst, page: _tf.lm_paged_cache_copy_block(
            mcfg, c, src, dst, page=page),
        has_recurrent_state=_tf.lm_has_recurrent_state(mcfg),
    )


def _vlm_api(mcfg) -> ModelAPI:
    dv = mcfg.d_frontend
    SI = mcfg.vision_tokens

    def make_batch(rng, B, N):
        St = N - SI
        toks = rng.integers(0, mcfg.vocab_size, (B, St), dtype=np.int32)
        pe = rng.standard_normal((B, SI, dv), dtype=np.float32)
        return {"tokens": jnp.asarray(toks),
                "patch_embeds": jnp.asarray(pe, dtype=mcfg.cdtype()),
                "labels": jnp.asarray(toks)}

    def batch_specs(B, N):
        St = N - SI
        return {"tokens": jax.ShapeDtypeStruct((B, St), jnp.int32),
                "patch_embeds": jax.ShapeDtypeStruct((B, SI, dv), mcfg.cdtype()),
                "labels": jax.ShapeDtypeStruct((B, St), jnp.int32)}

    def cache_init(B, S, dtype=jnp.bfloat16):
        return _tf.lm_cache_init(mcfg, B, S, dtype)

    return ModelAPI(
        mcfg=mcfg,
        init=lambda key: _vlm.vlm_init(key, mcfg),
        loss=lambda p, b: _vlm.vlm_loss(p, b, mcfg=mcfg),
        forward=lambda p, b: _vlm.vlm_apply(p, b["tokens"], b["patch_embeds"],
                                            mcfg=mcfg)[0],
        make_batch=make_batch,
        batch_specs=batch_specs,
        cache_init=cache_init,
        cache_specs=lambda B, S, dtype=jnp.bfloat16: jax.eval_shape(
            lambda: cache_init(B, S, dtype)),
        # decode runs on the LM backbone (vision is prefill-only)
        decode_step=lambda p, tok, c: _tf.lm_decode_step(p["lm"], tok, c, mcfg=mcfg),
    )


def _encdec_api(mcfg) -> ModelAPI:
    df = mcfg.d_frontend

    def make_batch(rng, B, N):
        Sd = max(N // mcfg.dec_ratio, 16)
        fr = rng.standard_normal((B, N, df), dtype=np.float32)
        toks = rng.integers(0, mcfg.vocab_size, (B, Sd), dtype=np.int32)
        return {"frames": jnp.asarray(fr, dtype=mcfg.cdtype()),
                "dec_tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}

    def batch_specs(B, N):
        Sd = max(N // mcfg.dec_ratio, 16)
        return {"frames": jax.ShapeDtypeStruct((B, N, df), mcfg.cdtype()),
                "dec_tokens": jax.ShapeDtypeStruct((B, Sd), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, Sd), jnp.int32)}

    def cache_specs(B, S, dtype=jnp.bfloat16):
        """Decoder self-attn caches (len S) + cross-attn memory K/V (len S)."""
        def build():
            mem = jnp.zeros((B, S, mcfg.d_model), mcfg.cdtype())
            p = jax.eval_shape(lambda k: _ed.encdec_init(k, mcfg),
                               jax.random.PRNGKey(0))
            # cache_init only needs shapes of dec_layers weights; build zeros
            pz = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), p)
            return _ed.encdec_cache_init(pz, mem, mcfg=mcfg, batch=B,
                                         max_len=S, dtype=dtype)
        return jax.eval_shape(build)

    def cache_init(B, S, dtype=jnp.bfloat16, params=None, memory=None):
        assert params is not None and memory is not None
        return _ed.encdec_cache_init(params, memory, mcfg=mcfg, batch=B,
                                     max_len=S, dtype=dtype)

    return ModelAPI(
        mcfg=mcfg,
        init=lambda key: _ed.encdec_init(key, mcfg),
        loss=lambda p, b: _ed.encdec_loss(p, b, mcfg=mcfg),
        forward=lambda p, b: _ed.decode_train(
            p, b["dec_tokens"], _ed.encode(p, b["frames"], mcfg=mcfg), mcfg=mcfg),
        make_batch=make_batch,
        batch_specs=batch_specs,
        cache_init=cache_init,
        cache_specs=cache_specs,
        decode_step=lambda p, tok, c: _ed.encdec_decode_step(p, tok, c, mcfg=mcfg),
    )


def _pc_api(mcfg) -> ModelAPI:
    def make_batch(rng, B, N):
        feats = rng.standard_normal((B, N, mcfg.in_dim), dtype=np.float32)
        tgt = rng.standard_normal((B, N, mcfg.out_dim), dtype=np.float32)
        mask = np.ones((B, N), bool)
        return {"feats": jnp.asarray(feats), "target": jnp.asarray(tgt),
                "mask": jnp.asarray(mask)}

    def batch_specs(B, N):
        return {"feats": jax.ShapeDtypeStruct((B, N, mcfg.in_dim), jnp.float32),
                "target": jax.ShapeDtypeStruct((B, N, mcfg.out_dim), jnp.float32),
                "mask": jax.ShapeDtypeStruct((B, N), jnp.bool_)}

    return ModelAPI(
        mcfg=mcfg,
        init=lambda key: _pc.pc_init(key, mcfg),
        loss=lambda p, b: _pc.pc_loss(p, b, mcfg=mcfg),
        forward=lambda p, b: _pc.pc_apply(p, b["feats"], mcfg=mcfg,
                                          mask=b.get("mask"),
                                          offsets=b.get("offsets")),
        make_batch=make_batch,
        batch_specs=batch_specs,
        forward_selection=lambda p, b: _pc.pc_apply(
            p, b["feats"], mcfg=mcfg, mask=b.get("mask"),
            offsets=b.get("offsets"), select=b.get("select"),
            return_selection=True) if mcfg.attention == "bsa" else None,
    )


def model_api(mcfg) -> ModelAPI:
    if mcfg.family in ("dense", "moe", "ssm", "hybrid"):
        return _lm_api(mcfg)
    if mcfg.family == "vlm":
        return _vlm_api(mcfg)
    if mcfg.family == "audio":
        return _encdec_api(mcfg)
    if mcfg.family == "pointcloud":
        return _pc_api(mcfg)
    raise ValueError(f"unknown family {mcfg.family}")
