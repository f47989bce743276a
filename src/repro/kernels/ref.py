"""Pure-jnp oracles for every Pallas kernel (tests assert allclose).

These re-export / adapt the reference implementations living in
``repro.core`` so each kernel has exactly one oracle.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.branches import NEG_INF, mask_to_bias, sdpa
from repro.core.bsa import ball_attention_ref  # noqa: F401
from repro.core.nsa_causal import local_window_attention_ref  # noqa: F401

__all__ = ["ball_attention_ref", "local_window_attention_ref",
           "flash_attention_ref", "selection_attention_ref"]


def flash_attention_ref(q, k, v, *, key_valid=None, causal=False,
                        block_causal=False, ell=1, bias=None):
    """Oracle for ops.flash_attention.  q:(B,N,H,D), k,v:(B,L,H,D)."""
    B, N, H, D = q.shape
    L = k.shape[1]
    b = jnp.zeros((B, 1, 1, L), jnp.float32)
    if key_valid is not None:
        b = b + mask_to_bias(key_valid[:, None, None, :])
    if bias is not None:
        b = b + bias.reshape(B, 1, 1, L).astype(jnp.float32)
    if causal:
        qi = jnp.arange(N)[:, None]
        ki = jnp.arange(L)[None, :]
        b = b + mask_to_bias((ki <= qi)[None, None])
    if block_causal:
        t = jnp.arange(N)[:, None]
        end = (jnp.arange(L)[None, :] + 1) * ell - 1
        b = b + mask_to_bias((end < t)[None, None])
    out = sdpa(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
               v.transpose(0, 2, 1, 3), b)
    return out.transpose(0, 2, 1, 3)


def selection_attention_ref(q, k, v, top_idx, sel_valid, mask, *,
                            block_size: int, group_size: int):
    """Oracle for ops.selection_attention (mirrors core's gather math,
    including the dead-group invalidation: all-padded query groups attend
    nothing and emit exact zeros, like the kernel's skipped tiles)."""
    from repro.kernels.occupancy import invalidate_dead_groups
    sel_valid = invalidate_dead_groups(sel_valid, mask, q.shape[1])
    B, N, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    ell = block_size
    nb = k.shape[1] // ell                   # keys may outnumber queries
    G = top_idx.shape[1]
    g = N // G
    kb = k.reshape(B, nb, ell, Hkv, D)
    vb = v.reshape(B, nb, ell, Hkv, D)
    bidx = jnp.arange(B)[:, None, None, None]
    safe_idx = jnp.where(sel_valid, top_idx, 0)
    kg = kb[bidx, safe_idx, :, jnp.arange(Hkv)[None, None, :, None], :]
    vg = vb[bidx, safe_idx, :, jnp.arange(Hkv)[None, None, :, None], :]
    L = top_idx.shape[-1] * ell
    kg = kg.reshape(B, G, Hkv, L, D)
    vg = vg.reshape(B, G, Hkv, L, D)
    key_valid = jnp.broadcast_to(sel_valid[..., None],
                                 (B, G, Hkv, top_idx.shape[-1], ell))
    if mask is not None:
        tok_valid = mask.reshape(B, nb, ell)
        tv = tok_valid[jnp.arange(B)[:, None, None, None], safe_idx]
        key_valid = key_valid & tv
    bias = mask_to_bias(key_valid.reshape(B, G, Hkv, 1, 1, L))
    qg = q.reshape(B, G, g, Hkv, rep, D).transpose(0, 1, 3, 4, 2, 5)
    logits = jnp.einsum("bgkrmd,bgkld->bgkrml", qg, kg,
                        preferred_element_type=jnp.float32) / (D ** 0.5)
    logits = logits + bias
    mx = jnp.maximum(logits.max(-1, keepdims=True), NEG_INF / 2)
    p = jnp.exp(logits - mx)
    p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)
    out = jnp.einsum("bgkrml,bgkld->bgkrmd", p.astype(v.dtype), vg,
                     preferred_element_type=jnp.float32).astype(v.dtype)
    return out.transpose(0, 1, 4, 2, 3, 5).reshape(B, N, Hq, D)
