"""Group-selected sparse attention Pallas kernel (the NSA/BSA hot path).

This is the TPU-native realization of the kernel the paper leaves as future
work ("we do not implement a Triton kernel for efficient selection").  The
per-group top-k block indices are **scalar-prefetched** to SMEM
(``pltpu.PrefetchScalarGridSpec``) and drive the K/V BlockSpec index maps, so
each grid step DMAs exactly one selected ℓ-sized KV block HBM→VMEM — a
contiguous burst, the TPU analogue of the paper's "KV blocks fetched in
contiguous chunks" cache-utilisation argument (§2.2 Group selection).

Grid: (B, Hkv, G, k*) with the selected-block index j innermost; flash-style
running-softmax scratch carries the accumulation across the k* blocks of a
group.  The M (rows) dimension of every matmul is the whole query group
(g positions × rep GQA heads), which is what keeps the MXU fed despite tiny
ℓ=8 blocks — exactly the hardware-alignment rationale of NSA group fetch.

Invalid selections are encoded as index −1: the index map clamps them to 0
(a harmless fetch) and the kernel skips their matmuls via ``pl.when`` in
BOTH directions — the backward's dead branch writes its dK/dV staging tiles
as exact zeros.  The selection front-ends additionally invalidate every
selection of an all-padding query group (``occupancy.invalidate_dead_groups``),
so a ragged batch's dead groups skip their whole k* sweep.

PRECISION CONTRACT (``common.resolve_compute_dtype``): operand tiles cast
to the compute dtype (bf16 in → bf16 through QK^T and PV, fp8 QK^T under
REPRO_FP8=1) while every ``dot_general`` accumulates fp32 and the softmax
statistics stay fp32.

Differentiable: the forward emits per-row logsumexp; the backward kernel
runs on the same scalar-prefetched grid, recomputes p = exp(s − lse) per
selected block, accumulates dQ across a group's k* blocks in scratch, and
writes per-selection dK/dV tiles to a (B, Hkv, G, k*, ℓ, D) staging buffer
that the VJP wrapper scatter-adds back through the gathered block indices
(duplicate selections of one block across groups sum correctly there).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (NEG_INF, interpret_batch_map, lse_finalize,
                                  mma_dtype, p_from_lse, resolve_compute_dtype,
                                  should_interpret)

__all__ = ["selection_attention_kernel_call"]


def _sel_id(idx_ref, b, h, g, j, dims):
    """Selected block id of grid cell (b, h, g, j).  The (B, Hkv, G, k*)
    index array rides in FLAT: SMEM pads a trailing dim to 128 words, so
    the 4-D form would cost 32× its size at k* = 4 (SMEM is 1 MiB)."""
    _, n_h, n_g, k_star = dims
    return idx_ref[((b * n_h + h) * n_g + g) * k_star + j]


def _fwd_kernel(idx_ref,                 # scalar prefetch (B·Hkv·G·k*,) int32
                q_ref, k_ref, v_ref, tokbias_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, dims: tuple, compute: str):
    b = pl.program_id(0)
    h = pl.program_id(1)
    g = pl.program_id(2)
    j = pl.program_id(3)
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = _sel_id(idx_ref, b, h, g, j, dims) >= 0

    @pl.when(valid)
    def _accumulate():
        q = q_ref[0, 0, 0].astype(sdt)                     # (M, D)
        k = k_ref[0, 0, 0].astype(sdt)                     # (ℓ, D)
        v = v_ref[0, 0, 0].astype(adt)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + tokbias_ref[0, 0]                          # (1, ℓ) padding bias
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        m_scr[...] = m_new
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(adt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == dims[3] - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-20)
        out = acc_scr[...] / denom
        out = jnp.where(l_scr[...] > 0.0, out, 0.0)        # all-invalid group → 0
        o_ref[0, 0, 0] = out.astype(o_ref.dtype)
        m_safe = jnp.maximum(m_scr[...], NEG_INF / 2)
        lse_ref[0, 0, 0] = lse_finalize(m_safe, l_scr[...])[:, 0][None]


def _bwd_kernel(idx_ref,
                q_ref, k_ref, v_ref, tokbias_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dkb_ref, dvb_ref, dq_scr, *,
                scale: float, dims: tuple, compute: str):
    b = pl.program_id(0)
    h = pl.program_id(1)
    g = pl.program_id(2)
    j = pl.program_id(3)
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    # Invalid selections fetched a clamped (harmless) block; their grid cell
    # skips all five matmuls and writes its dkb/dvb staging tiles as exact
    # zeros — p ≡ 0 there in the oracle, so the skip is bit-exact.
    valid = _sel_id(idx_ref, b, h, g, j, dims) >= 0

    @pl.when(valid)
    def _live_sel():
        q = q_ref[0, 0, 0].astype(sdt)                     # (M, D)
        k = k_ref[0, 0, 0].astype(sdt)                     # (ℓ, D)
        v = v_ref[0, 0, 0].astype(adt)
        do = do_ref[0, 0, 0].astype(adt)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + tokbias_ref[0, 0]
        p = p_from_lse(s, lse_ref[0, 0, 0].reshape(-1, 1))  # (M, ℓ)
        dvb_ref[0, 0, 0, 0] = jax.lax.dot_general(
            p.astype(adt), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dvb_ref.dtype)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, 0].reshape(-1, 1)) * scale
        dkb_ref[0, 0, 0, 0] = jax.lax.dot_general(
            ds.astype(adt), q_ref[0, 0, 0].astype(adt),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dkb_ref.dtype)
        dq_scr[...] += jax.lax.dot_general(ds.astype(adt), k.astype(adt),
                                           (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_not(valid))
    def _dead_sel():
        dvb_ref[0, 0, 0, 0] = jnp.zeros_like(dvb_ref[0, 0, 0, 0])
        dkb_ref[0, 0, 0, 0] = jnp.zeros_like(dkb_ref[0, 0, 0, 0])

    @pl.when(j == dims[3] - 1)
    def _finalize():
        dq_ref[0, 0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _fwd_call(q, kb, vb, idx, tok_bias, *, interpret, compute):
    B, Hkv, G, M, D = q.shape
    ell = kb.shape[3]
    k_star = idx.shape[-1]
    dims = idx.shape

    def q_map(b, h, g, j, idx_ref):
        return (b, h, g, 0, 0)

    def kv_map(b, h, g, j, idx_ref):
        return (b, h, jnp.maximum(_sel_id(idx_ref, b, h, g, j, dims), 0), 0, 0)

    def tok_map(b, h, g, j, idx_ref):
        return (b, jnp.maximum(_sel_id(idx_ref, b, h, g, j, dims), 0), 0, 0)

    def lse_map(b, h, g, j, idx_ref):
        return (b, h, g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, G, k_star),
        in_specs=[
            pl.BlockSpec((1, 1, 1, M, D), q_map),
            pl.BlockSpec((1, 1, 1, ell, D), kv_map),
            pl.BlockSpec((1, 1, 1, ell, D), kv_map),
            pl.BlockSpec((1, 1, 1, ell), tok_map),
        ],
        out_specs=(pl.BlockSpec((1, 1, 1, M, D), q_map),
                   pl.BlockSpec((1, 1, 1, 1, M), lse_map)),
        scratch_shapes=[
            pltpu.VMEM((M, 1), jnp.float32),
            pltpu.VMEM((M, 1), jnp.float32),
            pltpu.VMEM((M, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / (D ** 0.5), dims=dims,
                          compute=compute),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, Hkv, G, M, D), q.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, G, 1, M), jnp.float32)),
        name="bsa_selection_fwd",
        interpret=interpret,
    )(idx.reshape(-1), q, kb, vb, tok_bias[:, :, None])


def _bwd_call(q, kb, vb, idx, tok_bias, do, lse, delta, *, interpret,
              compute):
    B, Hkv, G, M, D = q.shape
    ell = kb.shape[3]
    k_star = idx.shape[-1]
    dims = idx.shape

    def q_map(b, h, g, j, idx_ref):
        return (b, h, g, 0, 0)

    def kv_map(b, h, g, j, idx_ref):
        return (b, h, jnp.maximum(_sel_id(idx_ref, b, h, g, j, dims), 0), 0, 0)

    def tok_map(b, h, g, j, idx_ref):
        return (b, jnp.maximum(_sel_id(idx_ref, b, h, g, j, dims), 0), 0, 0)

    def row_map(b, h, g, j, idx_ref):
        return (b, h, g, 0, 0)

    def sel_map(b, h, g, j, idx_ref):
        return (b, h, g, j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, G, k_star),
        in_specs=[
            pl.BlockSpec((1, 1, 1, M, D), q_map),
            pl.BlockSpec((1, 1, 1, ell, D), kv_map),
            pl.BlockSpec((1, 1, 1, ell, D), kv_map),
            pl.BlockSpec((1, 1, 1, ell), tok_map),
            pl.BlockSpec((1, 1, 1, M, D), q_map),
            pl.BlockSpec((1, 1, 1, 1, M), row_map),
            pl.BlockSpec((1, 1, 1, 1, M), row_map),
        ],
        out_specs=(pl.BlockSpec((1, 1, 1, M, D), q_map),
                   pl.BlockSpec((1, 1, 1, 1, ell, D), sel_map),
                   pl.BlockSpec((1, 1, 1, 1, ell, D), sel_map)),
        scratch_shapes=[pltpu.VMEM((M, D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=1.0 / (D ** 0.5), dims=dims,
                          compute=compute),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, Hkv, G, M, D), q.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, G, k_star, ell, D), kb.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, G, k_star, ell, D), vb.dtype)),
        name="bsa_selection_bwd",
        interpret=interpret,
    )(idx.reshape(-1), q, kb, vb, tok_bias[:, :, None], do, lse,
      delta[:, :, :, None])


def _scatter_blocks(d_sel, idx, nb: int):
    """Scatter-add per-selection tiles (B,Hkv,G,k*,ℓ,D) back to (B,Hkv,NB,ℓ,D).

    Duplicate selections of one block (across groups) sum; invalid (−1)
    selections were already zeroed by the backward kernel's validity gate but
    are routed to block 0 with zero contribution anyway.
    """
    B, Hkv, G, k_star, ell, D = d_sel.shape
    # one scatter over the flattened (B, Hkv, NB) block axis, ids offset by
    # their (b, h) slot: the compiler flattens a vmapped scatter into a new
    # instruction that loses its op_name, and with it its profiler scope
    slot = jnp.arange(B * Hkv, dtype=idx.dtype).reshape(B, Hkv, 1) * nb
    tgt = (jnp.maximum(idx.reshape(B, Hkv, G * k_star), 0) + slot).reshape(-1)
    zeros = jnp.zeros((B * Hkv * nb, ell, D), d_sel.dtype)
    out = zeros.at[tgt].add(d_sel.reshape(-1, ell, D))
    return out.reshape(B, Hkv, nb, ell, D)


@functools.lru_cache(maxsize=None)
def _make_vjp(interpret: bool, compute: str):
    kw = dict(interpret=interpret, compute=compute)

    @jax.custom_vjp
    def attend(q, kb, vb, idx, tok_bias):
        return _fwd_call(q, kb, vb, idx, tok_bias, **kw)[0]

    def attend_fwd(q, kb, vb, idx, tok_bias):
        o, lse = _fwd_call(q, kb, vb, idx, tok_bias, **kw)
        return o, (q, kb, vb, idx, tok_bias, o, lse)

    def attend_bwd(res, do):
        q, kb, vb, idx, tok_bias, o, lse = res
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        dq, dkb_sel, dvb_sel = _bwd_call(q, kb, vb, idx, tok_bias, do, lse,
                                         delta, **kw)
        nb = kb.shape[2]
        dkb = _scatter_blocks(dkb_sel, idx, nb)
        dvb = _scatter_blocks(dvb_sel, idx, nb)
        return dq, dkb, dvb, None, None                    # idx/bias: no grad

    attend.defvjp(attend_fwd, attend_bwd)
    return attend


@functools.partial(jax.jit, static_argnames=("interpret", "compute"))
def selection_attention_kernel_call(q, kb, vb, idx, tok_bias, *,
                                    interpret: bool | None = None,
                                    compute: str | None = None):
    """Compute group-selected attention.

    q:        (B, Hkv, G, M, D)   query groups (M = g·rep rows)
    kb, vb:   (B, Hkv, NB, ℓ, D)  blocked keys/values
    idx:      (B, Hkv, G, k*) int32 selected block ids, −1 ⇒ invalid
    tok_bias: (B, NB, ℓ) fp32 additive key-padding bias (0 / NEG_INF)
    returns   (B, Hkv, G, M, D)

    Differentiable in q, kb, vb.
    """
    if interpret is None:
        interpret = should_interpret()
    if compute is None:
        compute = resolve_compute_dtype(q.dtype)
    f = _make_vjp(interpret, compute)
    if interpret and q.shape[0] > 1:
        # CPU fallback: per-sample grids keep the interpreter linear in B
        f = functools.partial(interpret_batch_map, f)
    # the ids of one launch must fit SMEM: split the (B, Hkv) grid axes into
    # independent launches (every grid cell reads only its own (b, h) slice)
    B, Hkv, G, k_star = idx.shape
    bc, hc = _smem_chunks(B, Hkv, G * k_star)
    return jnp.concatenate([
        jnp.concatenate([
            f(q[b:b + bc, h:h + hc], kb[b:b + bc, h:h + hc],
              vb[b:b + bc, h:h + hc], idx[b:b + bc, h:h + hc],
              tok_bias[b:b + bc])
            for h in range(0, Hkv, hc)], axis=1)
        for b in range(0, B, bc)], axis=0)


# Budget for the flat scalar-prefetched ids of one launch: half of the
# 1 MiB SMEM, leaving the rest to Mosaic's own scalars.
_SMEM_ID_WORDS = 1 << 17


def _smem_chunks(B: int, Hkv: int, per_head: int) -> tuple[int, int]:
    """(batch, KV-head) chunk sizes whose B·Hkv·G·k* ids fit the budget."""
    hc = max(1, min(Hkv, _SMEM_ID_WORDS // per_head))
    bc = max(1, _SMEM_ID_WORDS // (per_head * Hkv)) if hc == Hkv else 1
    return min(bc, B), hc
