"""Blocked local causal attention Pallas kernel (the LM 'ball' branch).

Query block i attends causally within block i and fully to block i−1 —
the TPU-aligned blocked equivalent of a sliding window.  The previous block
is fetched by passing K (and V) twice with two index maps (self / prev).

GQA-NATIVE: the grid iterates KV heads.  Queries arrive as
(B·Hkv, rep, N, D); one grid step holds the group's fused (rep·w, D) query
tile and the (w, D) self / prev key tiles in VMEM — the K/V fetch is shared
by all ``rep`` query heads of the GQA group instead of being duplicated per
head.

Key-validity masking for ragged batches rides the same fetch pattern: the
per-token additive bias row (B, N) fp32 (0 valid / NEG_INF padding) is
passed twice with the self / prev index maps and added in LOGIT space before
the softmax — identical semantics to the bta/flash kernels, so a packed
batch of mixed-size sequences is one grid launch.

TILE-OCCUPANCY SKIPPING at HALF-TILE granularity (``kernels/occupancy.py``):
per-block any-valid-key verdicts (B, n_b) int32 ride in as a SCALAR-PREFETCH
operand, beside the per-block segment ids of packed-varlen batches (also
(B, n_b) int32: read as scalars, a (1, 1) VMEM block would not lower).  The
forward streams the prev half and the self half as two
separately ``pl.when``-guarded softmax steps over shared m/l/acc scratch —
a block whose prev neighbour is all-masked (or absent: block 0 / a packed
sample boundary) computes only the self half; a block whose own keys are
all masked skips that half too.  A fully dead block finalizes the zeroed
scratch to zeros with lse = LSE_EMPTY — exactly the jnp oracle's
all-masked-row output, so skipping is bit-exact.  The backward guards its
three contributions the same way (prev→dQ, self→dQ+dK/dV, next→dK/dV).

PRECISION CONTRACT (``common.resolve_compute_dtype``): operand tiles cast
to the compute dtype (fp32 in → fp32, bf16 in → bf16 through QK^T and PV,
fp8 for QK^T operands under REPRO_FP8=1) while every ``dot_general``
accumulates fp32 and softmax statistics stay fp32.

Differentiable: forward also emits per-row logsumexp (B·Hkv, rep, N).  The
backward is a single-pass per-block kernel — dQ of block i needs K/V of
blocks {i−1, i} (already the forward fetch pattern), while dK/dV of block i
get contributions from query blocks {i, i+1}; the NEXT query block (with its
dO/lse/delta rows) is fetched via a second set of index maps, so each grid
cell owns its output blocks outright and no cross-cell accumulation is
needed.  dK/dV sum over the group's rep query heads inside the
(rep·w)-row contractions.  The key bias enters the recomputed logits of both
contributions, so masked keys get exactly zero gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (NEG_INF, interpret_batch_map, lse_finalize,
                                  mma_dtype, p_from_lse, resolve_compute_dtype,
                                  rows_to_column, should_interpret)
from repro.kernels.occupancy import key_tile_live

__all__ = ["local_window_kernel_call"]


def _causal_mask(s, *, rows, w):
    """Within-block causal mask for one (rep·w, w) self-half tile.  Row r is
    query position r % w (rep-major layout), so every GQA head of the group
    shares one mask row."""
    qi = jax.lax.broadcasted_iota(jnp.int32, (rows, w), 0) % w
    ki = jax.lax.broadcasted_iota(jnp.int32, (rows, w), 1)
    return jnp.where(ki <= qi, s, NEG_INF)


def _fwd_kernel(kvl_ref, seg_ref, q_ref, ks_ref, vs_ref, kp_ref, vp_ref,
                bs_ref, bp_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, w: int, nh: int, compute: str):
    b = pl.program_id(0)
    i = pl.program_id(1)
    rep, _, D = q_ref.shape[1:]
    rows = rep * w
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))
    sb = b // nh
    live_self = kvl_ref[sb, i] != 0
    live_prev = ((i > 0) & (seg_ref[sb, jnp.maximum(i - 1, 0)] == seg_ref[sb, i])
                 & (kvl_ref[sb, jnp.maximum(i - 1, 0)] != 0))

    # one visit per grid cell — init unconditionally, halves merge into it
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(sdt).reshape(rows, D)              # (rep·w, D)

    def _half(k_half, v_half, bias_half, self_half):
        s = jax.lax.dot_general(q, k_half.astype(sdt), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias_half
        if self_half:
            s = _causal_mask(s, rows=rows, w=w)
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
            p.astype(adt), v_half.astype(adt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live_prev)
    def _prev_half():
        _half(kp_ref[0], vp_ref[0], bp_ref[0], self_half=False)

    @pl.when(live_self)
    def _self_half():
        _half(ks_ref[0], vs_ref[0], bs_ref[0], self_half=True)

    denom = jnp.maximum(l_scr[...], 1e-20)                 # dead block → zeros
    o_ref[0] = (acc_scr[...] / denom).reshape(rep, w, D).astype(o_ref.dtype)
    m_safe_f = jnp.maximum(m_scr[...], NEG_INF / 2)
    lse_ref[0] = lse_finalize(m_safe_f, l_scr[...])[:, 0].reshape(rep, w)


def _bwd_kernel(kvl_ref, seg_ref, qs_ref, qn_ref, ks_ref, kp_ref, vs_ref,
                vp_ref, bs_ref, bp_ref, dos_ref, don_ref, lses_ref, lsen_ref,
                dels_ref, deln_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                scale: float, w: int, n_b: int, nh: int, compute: str):
    b = pl.program_id(0)
    i = pl.program_id(1)
    rep, _, D = qs_ref.shape[1:]
    rows = rep * w
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))
    sb = b // nh
    live_self = kvl_ref[sb, i] != 0                        # my keys carry weight
    seg = seg_ref[sb, i]
    live_prev = ((i > 0) & (seg_ref[sb, jnp.maximum(i - 1, 0)] == seg)
                 & (kvl_ref[sb, jnp.maximum(i - 1, 0)] != 0))
    # next block's queries contribute to MY dK/dV iff my keys are valid and a
    # real same-sample next block exists
    live_next = ((i < n_b - 1)
                 & (seg_ref[sb, jnp.minimum(i + 1, n_b - 1)] == seg) & live_self)

    dq_scr[...] = jnp.zeros_like(dq_scr)
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)

    qs = qs_ref[0].astype(sdt).reshape(rows, D)            # (rep·w, D)
    dos = dos_ref[0].astype(adt).reshape(rows, D)
    lses = rows_to_column(lses_ref[0])
    dels = rows_to_column(dels_ref[0])

    @pl.when(live_prev)
    def _prev_half():                                      # prev keys → my dQ
        kp = kp_ref[0]
        s = jax.lax.dot_general(qs, kp.astype(sdt), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bp_ref[0]                                  # prev half: fully visible
        p = p_from_lse(s, lses)                            # (rep·w, w)
        dp = jax.lax.dot_general(dos, vp_ref[0].astype(adt),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dels) * scale
        dq_scr[...] += jax.lax.dot_general(ds.astype(adt), kp.astype(adt),
                                           (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(live_self)
    def _self_half():                                      # my keys → dQ, dK, dV
        ks = ks_ref[0]
        vs = vs_ref[0].astype(adt)
        s = jax.lax.dot_general(qs, ks.astype(sdt), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bs_ref[0]
        s = _causal_mask(s, rows=rows, w=w)
        p = p_from_lse(s, lses)                            # (rep·w, w)
        dp = jax.lax.dot_general(dos, vs, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dels) * scale
        dq_scr[...] += jax.lax.dot_general(ds.astype(adt), ks.astype(adt),
                                           (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        # the (0,)-axis contraction sums the group's rep·w rows
        dv_scr[...] += jax.lax.dot_general(p.astype(adt), dos,
                                           (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(adt), qs_ref[0].astype(adt).reshape(rows, D),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(live_next)
    def _next_part():                                      # next queries → my dK/dV
        qn = qn_ref[0].astype(sdt).reshape(rows, D)
        don = don_ref[0].astype(adt).reshape(rows, D)
        # query block i+1 sees block i as its fully-visible prev half; its
        # logits here were part of its forward softmax, so exp(sn − lse) ≤ 1
        sn = jax.lax.dot_general(qn, ks_ref[0].astype(sdt),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        sn = sn + bs_ref[0]
        pn = p_from_lse(sn, rows_to_column(lsen_ref[0]))  # (rep·w, w)
        dv_scr[...] += jax.lax.dot_general(pn.astype(adt), don,
                                           (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dpn = jax.lax.dot_general(don, vs_ref[0].astype(adt),
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dsn = pn * (dpn - rows_to_column(deln_ref[0])) * scale
        dk_scr[...] += jax.lax.dot_general(
            dsn.astype(adt), qn_ref[0].astype(adt).reshape(rows, D),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq_ref[0] = dq_scr[...].reshape(rep, w, D).astype(dq_ref.dtype)
    dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _fwd_call(q, k, v, key_bias, blk_seg, kv_live, *, window, n_heads,
              interpret, compute):
    BH, rep, N, D = q.shape
    w = window
    H = n_heads                                            # KV heads
    assert N % w == 0
    n_b = N // w
    q_blk = pl.BlockSpec((1, rep, w, D), lambda b, i, lv, sg: (b, 0, i, 0))
    self_blk = pl.BlockSpec((1, w, D), lambda b, i, lv, sg: (b, i, 0))
    prev_blk = pl.BlockSpec((1, w, D),
                            lambda b, i, lv, sg: (b, jnp.maximum(i - 1, 0), 0))
    bias_self = pl.BlockSpec((1, 1, w), lambda b, i, lv, sg: (b // H, 0, i))
    bias_prev = pl.BlockSpec(
        (1, 1, w), lambda b, i, lv, sg: (b // H, 0, jnp.maximum(i - 1, 0)))
    lse_blk = pl.BlockSpec((1, rep, w), lambda b, i, lv, sg: (b, 0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                             # liveness, block seg ids
        grid=(BH, n_b),
        in_specs=[q_blk, self_blk, self_blk, prev_blk, prev_blk,
                  bias_self, bias_prev],
        out_specs=(q_blk, lse_blk),
        scratch_shapes=[
            pltpu.VMEM((rep * w, 1), jnp.float32),
            pltpu.VMEM((rep * w, 1), jnp.float32),
            pltpu.VMEM((rep * w, D), jnp.float32),
        ],
    )
    kb = key_bias[:, None]                                 # (B, 1, N)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / (D ** 0.5), w=w, nh=H,
                          compute=compute),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((BH, rep, N, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, rep, N), jnp.float32)),
        name="bsa_local_fwd",
        interpret=interpret,
    )(kv_live, blk_seg, q, k, v, k, v, kb, kb)


def _bwd_call(q, k, v, key_bias, blk_seg, kv_live, do, lse, delta, *, window,
              n_heads, interpret, compute):
    BH, rep, N, D = q.shape
    w = window
    H = n_heads
    n_b = N // w

    def nxt(i):
        return jnp.minimum(i + 1, n_b - 1)

    def prv(i):
        return jnp.maximum(i - 1, 0)

    q_self = pl.BlockSpec((1, rep, w, D), lambda b, i, lv, sg: (b, 0, i, 0))
    q_next = pl.BlockSpec((1, rep, w, D), lambda b, i, lv, sg: (b, 0, nxt(i), 0))
    self_blk = pl.BlockSpec((1, w, D), lambda b, i, lv, sg: (b, i, 0))
    prev_blk = pl.BlockSpec((1, w, D), lambda b, i, lv, sg: (b, prv(i), 0))
    bias_self = pl.BlockSpec((1, 1, w), lambda b, i, lv, sg: (b // H, 0, i))
    bias_prev = pl.BlockSpec((1, 1, w),
                             lambda b, i, lv, sg: (b // H, 0, prv(i)))
    row_self = pl.BlockSpec((1, rep, w), lambda b, i, lv, sg: (b, 0, i))
    row_next = pl.BlockSpec((1, rep, w), lambda b, i, lv, sg: (b, 0, nxt(i)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                             # liveness, block seg ids
        grid=(BH, n_b),
        in_specs=[q_self, q_next,                # q self / next
                  self_blk, prev_blk,            # k self / prev
                  self_blk, prev_blk,            # v self / prev
                  bias_self, bias_prev,          # key bias self / prev
                  q_self, q_next,                # do self / next
                  row_self, row_next,            # lse self / next
                  row_self, row_next],           # delta self / next
        out_specs=(q_self, self_blk, self_blk),
        scratch_shapes=[
            pltpu.VMEM((rep * w, D), jnp.float32),
            pltpu.VMEM((w, D), jnp.float32),
            pltpu.VMEM((w, D), jnp.float32),
        ],
    )
    kb = key_bias[:, None]                                 # (B, 1, N)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=1.0 / (D ** 0.5), w=w, n_b=n_b,
                          nh=H, compute=compute),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((BH, rep, N, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, N, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, N, D), v.dtype)),
        name="bsa_local_bwd",
        interpret=interpret,
    )(kv_live, blk_seg, q, q, k, k, v, v, kb, kb, do, do, lse, lse, delta,
      delta)


@functools.lru_cache(maxsize=None)
def _make_vjp(window: int, n_heads: int, interpret: bool, compute: str):
    kw = dict(window=window, n_heads=n_heads, interpret=interpret,
              compute=compute)

    @jax.custom_vjp
    def attend(q, k, v, key_bias, blk_seg, kv_live):
        return _fwd_call(q, k, v, key_bias, blk_seg, kv_live, **kw)[0]

    def attend_fwd(q, k, v, key_bias, blk_seg, kv_live):
        o, lse = _fwd_call(q, k, v, key_bias, blk_seg, kv_live, **kw)
        return o, (q, k, v, key_bias, blk_seg, kv_live, o, lse)

    def attend_bwd(res, do):
        q, k, v, key_bias, blk_seg, kv_live, o, lse = res
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        dq, dk, dv = _bwd_call(q, k, v, key_bias, blk_seg, kv_live, do, lse,
                               delta, **kw)
        return dq, dk, dv, None, None, None                # bias/seg/live: no grad

    attend.defvjp(attend_fwd, attend_bwd)
    return attend


@functools.partial(jax.jit, static_argnames=("window", "n_heads", "interpret",
                                             "compute"))
def local_window_kernel_call(q, k, v, key_bias, *, window: int, n_heads: int,
                             interpret: bool | None = None, blk_seg=None,
                             compute: str | None = None):
    """q: (B·Hkv, rep, N, D) grouped queries; k, v: (B·Hkv, N, D) — one K/V
    stream per KV head shared by its rep query heads; key_bias: (B, N) fp32
    additive (0 valid / NEG_INF padding); ``n_heads`` is the KV head count.
    ``blk_seg``: optional (B, N/window) int32 per-block segment ids for
    PACKED-VARLEN batches — a block never attends a prev block of a
    different segment, and its keys get no gradient from a next block of a
    different segment (None = one segment, the dense behaviour).
    ``compute``: canonical matmul-operand dtype name (None resolves from
    q.dtype).  Per-block key liveness is derived from ``key_bias`` and
    scalar-prefetched: all-masked self / prev halves are skipped exactly.
    Returns (B·Hkv, rep, N, D).
    Differentiable in q, k, v (bias and segment ids carry no gradient)."""
    if interpret is None:
        interpret = should_interpret()
    if compute is None:
        compute = resolve_compute_dtype(q.dtype)
    if blk_seg is None:
        blk_seg = jnp.zeros((key_bias.shape[0], q.shape[2] // window),
                            jnp.int32)
    kv_live = key_tile_live(key_bias, window).astype(jnp.int32)  # (B, n_b)
    if interpret and q.shape[0] > 1:
        # CPU fallback: per-slice grids keep the interpreter linear in B·Hkv
        bias_bh = jnp.repeat(key_bias, n_heads, axis=0)
        seg_bh = jnp.repeat(blk_seg, n_heads, axis=0)
        live_bh = jnp.repeat(kv_live, n_heads, axis=0)
        return interpret_batch_map(_make_vjp(window, 1, True, compute),
                                   q, k, v, bias_bh, seg_bh, live_bh)
    return _make_vjp(window, n_heads, interpret, compute)(
        q, k, v, key_bias, blk_seg, kv_live)
