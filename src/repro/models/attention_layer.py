"""Attention layer: QKV/O projections + RoPE + attention dispatch.

One layer serves all model families; the attention MECHANISM (``bsa`` |
``full`` | ``erwin``, ``mcfg.attention``) and causality are chosen by the
caller, while the execution BACKEND (jnp / pallas / interpret / plug-in,
``mcfg.bsa.backend`` — see ``repro.core.backend``) is orthogonal and applies
to every mechanism.  Decode steps share the same projections and route
through ``core.nsa_causal_decode`` (sparse) or a dense cached path (full
attention).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import (
    bsa_attention,
    bsa_attention_varlen,
    bsa_init,
    erwin_attention,
    full_attention,
    init_decode_cache,
    init_paged_decode_cache,
    nsa_causal_attention,
    nsa_causal_decode,
    nsa_causal_decode_paged,
)
from repro.core.branches import repeat_kv, sdpa, mask_to_bias
from repro.layers.nn import dense, dense_init
from repro.layers.rope import apply_rope


def attention_layer_init(key, mcfg, *, param_dtype) -> dict:
    d = mcfg.d_model
    hd = mcfg.resolved_head_dim
    kq, kk, kv, ko, kb = jax.random.split(key, 5)
    p = {
        "wq": dense_init(kq, d, mcfg.n_heads * hd, param_dtype=param_dtype),
        "wk": dense_init(kk, d, mcfg.n_kv_heads * hd, param_dtype=param_dtype),
        "wv": dense_init(kv, d, mcfg.n_kv_heads * hd, param_dtype=param_dtype),
        "wo": dense_init(ko, mcfg.n_heads * hd, d, param_dtype=param_dtype),
    }
    if mcfg.attention == "bsa":
        init_fn = bsa_init  # same param structure as nsa_init
        p["bsa"] = init_fn(kb, mcfg.bsa, n_heads=mcfg.n_heads,
                           n_kv_heads=mcfg.n_kv_heads, head_dim=hd,
                           d_model=d, param_dtype=param_dtype)
    return p


def _project(p, x, mcfg, positions=None, rope: bool = True):
    B, N, _ = x.shape
    hd = mcfg.resolved_head_dim
    q = dense(p["wq"], x).reshape(B, N, mcfg.n_heads, hd)
    k = dense(p["wk"], x).reshape(B, N, mcfg.n_kv_heads, hd)
    v = dense(p["wv"], x).reshape(B, N, mcfg.n_kv_heads, hd)
    if rope and positions is not None:
        q = apply_rope(q, positions, mcfg.rope_theta)
        k = apply_rope(k, positions, mcfg.rope_theta)
    return q, k, v


def attention_layer_apply(p, x, *, mcfg, causal: bool, mask=None,
                          positions=None, rope: bool = True,
                          erwin_level: int = 0, offsets=None, select=None,
                          return_selection: bool = False):
    """Full-sequence forward.  x: (B, N, d_model) → (B, N, d_model).

    ``offsets`` (S+1,) int32 switches the non-causal BSA path to the
    PACKED-VARLEN layout (docs/varlen.md): x must then be a single packed
    row (B == 1) whose samples are concatenated back-to-back at ball-size
    boundaries, and ``mask``'s row marks real tokens.  Other mechanisms
    don't support it (yet) and raise.

    ``return_selection`` (non-causal BSA only) also returns the selection
    branch's block ids, ``{"indices": (B, G, Hkv, k*)}``; ``select`` (same
    shape) replays ids instead, adding ``gap``/``flips``
    (``core.bsa._select_blocks``).
    """
    B, N, _ = x.shape
    with jax.named_scope("attn_proj"):
        q, k, v = _project(p, x, mcfg, positions, rope)
    want_sel = return_selection or select is not None
    if want_sel and (mcfg.attention != "bsa" or causal):
        raise NotImplementedError(
            "selection ids exist only in non-causal BSA "
            f"(got attention={mcfg.attention!r}, causal={causal})")
    aux = {}
    if offsets is not None:
        if mcfg.attention != "bsa" or causal:
            raise NotImplementedError(
                "packed-varlen offsets are only supported by non-causal BSA "
                f"(got attention={mcfg.attention!r}, causal={causal})")
        if B != 1:
            raise ValueError(
                f"packed-varlen input must be a single packed row, got B={B}")
        out = bsa_attention_varlen(
            p["bsa"], q[0], k[0], v[0], cfg=mcfg.bsa, offsets=offsets,
            mask=None if mask is None else mask[0], x=x[0],
            return_aux=want_sel, select=None if select is None else select[0])
        if want_sel:
            out, aux = out
            aux["indices"] = aux["indices"][None]
        out = out[None]
    elif mcfg.attention == "bsa":
        if causal:
            out = nsa_causal_attention(p["bsa"], q, k, v, cfg=mcfg.bsa,
                                       mask=mask, x=x)
        else:
            out = bsa_attention(p["bsa"], q, k, v, cfg=mcfg.bsa, mask=mask,
                                x=x, return_aux=want_sel, select=select)
            if want_sel:
                out, aux = out
    elif mcfg.attention == "erwin":
        out = erwin_attention(q, k, v, ball_size=mcfg.bsa.ball_size,
                              level=erwin_level, mask=mask,
                              backend=mcfg.bsa.backend)
    else:
        out = full_attention(q, k, v, mask=mask, causal=causal,
                             backend=mcfg.bsa.backend)
    out = out.reshape(B, N, mcfg.n_heads * mcfg.resolved_head_dim)
    with jax.named_scope("attn_proj"):
        out = dense(p["wo"], out)
    if want_sel:
        return out, {key: aux[key] for key in ("indices", "gap", "flips")
                     if key in aux}
    return out


def cross_attention_apply(p, x, memory_kv, *, mcfg, mem_mask=None):
    """Cross-attention with precomputed memory K/V: (B, L, Hkv, D) pair."""
    B, N, _ = x.shape
    hd = mcfg.resolved_head_dim
    q = dense(p["wq"], x).reshape(B, N, mcfg.n_heads, hd)
    mk, mv = memory_kv
    out = full_attention(q, mk, mv, mask=mem_mask, causal=False,
                         backend=mcfg.bsa.backend)
    return dense(p["wo"], out.reshape(B, N, mcfg.n_heads * hd))


def memory_kv(p, memory, *, mcfg):
    """Precompute cross-attention K/V from encoder output (B, L, d)."""
    B, L, _ = memory.shape
    hd = mcfg.resolved_head_dim
    mk = dense(p["wk"], memory).reshape(B, L, mcfg.n_kv_heads, hd)
    mv = dense(p["wv"], memory).reshape(B, L, mcfg.n_kv_heads, hd)
    return mk, mv


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def attention_cache_init(mcfg, batch: int, max_len: int, dtype) -> dict:
    hd = mcfg.resolved_head_dim
    if mcfg.attention == "bsa":
        return init_decode_cache(batch, max_len, mcfg.n_kv_heads, hd,
                                 mcfg.bsa, dtype=dtype)
    return {
        "k": jnp.zeros((batch, max_len, mcfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, mcfg.n_kv_heads, hd), dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def attention_paged_cache_init(mcfg, num_blocks: int, page: int, dtype) -> dict:
    """Flat paged KV pools for one attention layer (+1 trash block).

    BSA layers carry token + φ-compressed pools (``init_paged_decode_cache``);
    full attention carries token pools only.  Block ids are SHARED across
    layers: every layer's pool has the same block layout, so one host-side
    block table serves the whole stack."""
    hd = mcfg.resolved_head_dim
    if mcfg.attention == "bsa":
        return init_paged_decode_cache(num_blocks, page, mcfg.n_kv_heads, hd,
                                       mcfg.bsa, dtype=dtype)
    R = (num_blocks + 1) * page
    return {
        "k": jnp.zeros((R, mcfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((R, mcfg.n_kv_heads, hd), dtype),
    }


def attention_layer_decode_paged(p, x1, cache, table, lengths, *, mcfg,
                                 page: int, rope: bool = True):
    """One-token decode against paged pools with PER-SLOT lengths.

    x1: (B, 1, d); ``table`` (B, n_pages) int32 block table; ``lengths``
    (B,) int32 per-slot positions (RoPE rotates each slot's query/key by its
    OWN position — the per-slot generalisation of the lockstep scalar).
    """
    B = x1.shape[0]
    pos = lengths[:, None].astype(jnp.int32)                         # (B,1)
    q, k, v = _project(p, x1, mcfg, pos if rope else None, rope)
    if mcfg.attention == "bsa":
        out, cache = nsa_causal_decode_paged(p["bsa"], q, k, v, cache, table,
                                             lengths, cfg=mcfg.bsa, page=page,
                                             x1=x1)
    else:
        n_pages = table.shape[1]
        capacity = n_pages * page
        wblk = jnp.take_along_axis(table, (lengths // page)[:, None], axis=1)
        wrow = wblk[:, 0] * page + lengths % page                    # (B,)
        kc = cache["k"].at[wrow].set(k[:, 0].astype(cache["k"].dtype))
        vc = cache["v"].at[wrow].set(v[:, 0].astype(cache["v"].dtype))
        apos = jnp.broadcast_to(jnp.arange(capacity)[None], (B, capacity))
        blk = jnp.take_along_axis(table, apos // page, axis=1)
        rows = blk * page + apos % page                              # (B,cap)
        k_all = kc[rows]                                             # (B,cap,Hkv,D)
        v_all = vc[rows]
        valid = apos <= lengths[:, None]
        rep = mcfg.n_heads // mcfg.n_kv_heads
        out = sdpa(q.transpose(0, 2, 1, 3),
                   repeat_kv(k_all.astype(q.dtype), rep).transpose(0, 2, 1, 3),
                   repeat_kv(v_all.astype(q.dtype), rep).transpose(0, 2, 1, 3),
                   mask_to_bias(valid[:, None, None, :])).transpose(0, 2, 1, 3)
        cache = {"k": kc, "v": vc}
    out = out.reshape(B, 1, mcfg.n_heads * mcfg.resolved_head_dim)
    return dense(p["wo"], out), cache


def attention_layer_decode(p, x1, cache, *, mcfg, rope: bool = True):
    """One-token decode.  x1: (B, 1, d) → (B, 1, d), updated cache."""
    B = x1.shape[0]
    t = cache["length"]
    pos = jnp.full((B, 1), t, jnp.int32)
    q, k, v = _project(p, x1, mcfg, pos if rope else None, rope)
    if mcfg.attention == "bsa":
        out, cache = nsa_causal_decode(p["bsa"], q, k, v, cache,
                                       cfg=mcfg.bsa, x1=x1)
    else:
        kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, t, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, t, 0, 0))
        S = kc.shape[1]
        valid = jnp.arange(S)[None, None, None, :] <= t
        rep = mcfg.n_heads // mcfg.n_kv_heads
        out = sdpa(q.transpose(0, 2, 1, 3),
                   repeat_kv(kc.astype(q.dtype), rep).transpose(0, 2, 1, 3),
                   repeat_kv(vc.astype(q.dtype), rep).transpose(0, 2, 1, 3),
                   mask_to_bias(valid)).transpose(0, 2, 1, 3)
        cache = {"k": kc, "v": vc, "length": t + 1}
    out = out.reshape(B, 1, mcfg.n_heads * mcfg.resolved_head_dim)
    return dense(p["wo"], out), cache
