"""Flash-attention Pallas kernel with the mask modes BSA needs.

Streaming softmax over K/V tiles (running max / sum / accumulator in VMEM
scratch).  Used for:

  * the COMPRESSION branch — queries vs φ-pooled coarse KV.  ``block_causal``
    (with ``ell`` = compression block length) masks coarse block j for query
    t unless the block ends strictly before t: (j+1)·ell − 1 < t.  The mask
    is generated in-kernel from indices and never materialised (an N × N/ℓ
    fp32 bias for 32k tokens would be 0.5 GB — this is why the bias is
    virtual).
  * FULL attention baseline — ``causal`` token mask.
  * both support an additive per-key bias row (B, L) fp32 for padding.

GQA-NATIVE: the grid iterates KV heads.  Queries arrive as
(B·Hkv, rep, N, D); each grid cell loads ONE (Tk, D) K/V tile and streams it
against the (rep·Tq, D) fused query rows of its GQA group — K/V HBM traffic
is divided by ``rep`` versus the head-repeated layout, and the rep× taller
matmul keeps the MXU fed.  Tile sizes (tq, tk) come from the caller
(``kernels/ops.py`` resolves them via the ``kernels/tuning.py`` autotuner
and PADS both axes to tile multiples, so arbitrary N/L are legal here as
long as tq | N and tk | L).

TILE-OCCUPANCY SKIPPING (``kernels/occupancy.py``): a host-precomputed
(B, nQ, nK) int32 liveness map rides in as a SCALAR-PREFETCH operand
(``pltpu.PrefetchScalarGridSpec``); ``pl.when(live)`` wraps the tile body in
the forward AND both backward kernels, so a grid cell whose key tile is all
masked / whose query tile is all padding / that the causal structure rules
out issues no matmuls at all.  Init and finalize stay unconditional: a query
tile none of whose cells were live finalizes to zeros with lse = LSE_EMPTY —
exactly what the jnp oracle produces for all-masked rows, so skipping is
bit-exact (outputs and gradients).

PRECISION CONTRACT (``common.resolve_compute_dtype``): operand tiles are
cast to the compute dtype — fp32 inputs compute fp32 (the historical
behaviour), bf16 inputs stay bf16 through QK^T and PV, fp8 (REPRO_FP8=1)
applies to the QK^T operands only — while every ``dot_general`` accumulates
fp32 via ``preferred_element_type`` and softmax statistics / lse / scratch
are always fp32.

Grid: (B·Hkv, nQ, nK) with K innermost.  Scratch: m, l: (rep·Tq, 1) fp32,
acc: (rep·Tq, D) fp32.  VMEM @ rep=4, Tq=Tk=256, D=128 ≈ 1.7 MiB.

Differentiable (FlashAttention-style recomputation backward): the forward
additionally emits per-row logsumexp (B·Hkv, rep, N); the backward
recomputes p = exp(s − lse) per tile in two kernels — a dQ kernel on the
forward grid (K innermost, dQ accumulated in scratch) and a dK/dV kernel on
the transposed grid (B·Hkv, nK, nQ) with Q innermost; dK/dV of a tile
accumulate over the group's rep query heads inside the (rep·Tq)-row
contraction itself, so each gradient stays a pure per-tile accumulation
with no cross-grid races.
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

__all__ = ["flash_attention_kernel_call"]


def _mask_logits(s, i, j, *, rows, tq, tk, causal, block_causal, ell):
    """Apply the virtual (index-generated) causal / block-causal mask.

    ``rows = rep·tq``: row r of the fused group tile is query position
    ``i·tq + r % tq`` (rep-major layout), so all rep heads of a group see
    the same mask row."""
    if not (causal or block_causal):
        return s
    qpos = i * tq + jax.lax.broadcasted_iota(jnp.int32, (rows, tk), 0) % tq
    kidx = j * tk + jax.lax.broadcasted_iota(jnp.int32, (rows, tk), 1)
    if block_causal:
        ok = (kidx + 1) * ell - 1 < qpos                   # coarse block ends before t
    else:
        ok = kidx <= qpos
    return jnp.where(ok, s, NEG_INF)


def _fwd_kernel(live_ref, q_ref, k_ref, v_ref, kbias_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                scale: float, n_k: int, tq: int, tk: int,
                causal: bool, block_causal: bool, ell: int,
                nh: int, compute: str):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    rep, _, D = q_ref.shape[1:]
    rows = rep * tq
    sdt = jnp.dtype(compute)                               # QK^T operand dtype
    adt = jnp.dtype(mma_dtype(compute))                    # PV operand dtype

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live_ref[b // nh, i, j] != 0)
    def _step():
        q = q_ref[0].astype(sdt).reshape(rows, D)          # (rep·Tq, D)
        k = k_ref[0].astype(sdt)                           # (Tk, D)
        v = v_ref[0].astype(adt)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + kbias_ref[0]                               # (1, Tk) key-validity bias
        s = _mask_logits(s, i, j, rows=rows, tq=tq, tk=tk, causal=causal,
                         block_causal=block_causal, ell=ell)

        m_prev = m_scr[...]                                # (rep·Tq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(adt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(j == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / denom).reshape(rep, tq, D).astype(o_ref.dtype)
        m_safe_f = jnp.maximum(m_scr[...], NEG_INF / 2)
        lse_ref[0] = lse_finalize(m_safe_f, l_scr[...])[:, 0].reshape(rep, tq)


def _dq_kernel(live_ref, q_ref, k_ref, v_ref, kbias_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, *,
               scale: float, n_k: int, tq: int, tk: int,
               causal: bool, block_causal: bool, ell: int,
               nh: int, compute: str):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    rep, _, D = q_ref.shape[1:]
    rows = rep * tq
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(live_ref[b // nh, i, j] != 0)
    def _step():
        q = q_ref[0].astype(sdt).reshape(rows, D)          # (rep·Tq, D)
        k = k_ref[0].astype(sdt)                           # (Tk, D)
        ka = k_ref[0].astype(adt)
        v = v_ref[0].astype(adt)
        do = do_ref[0].astype(adt).reshape(rows, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + kbias_ref[0]
        s = _mask_logits(s, i, j, rows=rows, tq=tq, tk=tk, causal=causal,
                         block_causal=block_causal, ell=ell)
        p = p_from_lse(s, rows_to_column(lse_ref[0]))     # (rep·Tq, Tk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - rows_to_column(delta_ref[0])) * scale
        dq_scr[...] += jax.lax.dot_general(ds.astype(adt), ka,
                                           (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(j == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].reshape(rep, tq, D).astype(dq_ref.dtype)


def _dkv_kernel(live_ref, q_ref, k_ref, v_ref, kbias_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale: float, n_q: int, tq: int, tk: int,
                causal: bool, block_causal: bool, ell: int,
                nh: int, compute: str):
    b = pl.program_id(0)
    j = pl.program_id(1)                                   # K tile (outer)
    i = pl.program_id(2)                                   # Q tile (inner)
    rep, _, D = q_ref.shape[1:]
    rows = rep * tq
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(live_ref[b // nh, i, j] != 0)
    def _step():
        q = q_ref[0].astype(sdt).reshape(rows, D)          # (rep·Tq, D)
        qa = q_ref[0].astype(adt).reshape(rows, D)
        k = k_ref[0].astype(sdt)                           # (Tk, D)
        v = v_ref[0].astype(adt)
        do = do_ref[0].astype(adt).reshape(rows, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + kbias_ref[0]
        s = _mask_logits(s, i, j, rows=rows, tq=tq, tk=tk, causal=causal,
                         block_causal=block_causal, ell=ell)
        p = p_from_lse(s, rows_to_column(lse_ref[0]))     # (rep·Tq, Tk)
        # the (0,)-axis contraction sums over rep·Tq rows: the GQA group's
        # dK/dV accumulation happens inside the matmul
        dv_scr[...] += jax.lax.dot_general(p.astype(adt), do,
                                           (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - rows_to_column(delta_ref[0])) * scale
        dk_scr[...] += jax.lax.dot_general(ds.astype(adt), qa,
                                           (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _fwd_call(q, k, v, key_bias, live, *, n_heads, tq, tk, causal,
              block_causal, ell, interpret, compute):
    BH, rep, N, D = q.shape
    L = k.shape[1]
    n_k = L // tk
    kern = functools.partial(_fwd_kernel, scale=1.0 / (D ** 0.5), n_k=n_k,
                             tq=tq, tk=tk, causal=causal,
                             block_causal=block_causal, ell=ell,
                             nh=n_heads, compute=compute)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, N // tq, n_k),
        in_specs=[
            pl.BlockSpec((1, rep, tq, D), lambda b, i, j, lv: (b, 0, i, 0)),
            pl.BlockSpec((1, tk, D), lambda b, i, j, lv: (b, j, 0)),
            pl.BlockSpec((1, tk, D), lambda b, i, j, lv: (b, j, 0)),
            pl.BlockSpec((1, 1, tk), lambda b, i, j, lv: (b // n_heads, 0, j)),
        ],
        out_specs=(pl.BlockSpec((1, rep, tq, D), lambda b, i, j, lv: (b, 0, i, 0)),
                   pl.BlockSpec((1, rep, tq), lambda b, i, j, lv: (b, 0, i))),
        scratch_shapes=[
            pltpu.VMEM((rep * tq, 1), jnp.float32),
            pltpu.VMEM((rep * tq, 1), jnp.float32),
            pltpu.VMEM((rep * tq, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((BH, rep, N, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, rep, N), jnp.float32)),
        name="bsa_flash_fwd",
        interpret=interpret,
    )(live, q, k, v, key_bias[:, None])


def _bwd_calls(q, k, v, key_bias, live, do, lse, delta, *, n_heads, tq, tk,
               causal, block_causal, ell, interpret, compute):
    BH, rep, N, D = q.shape
    L = k.shape[1]
    H = n_heads
    n_q, n_k = N // tq, L // tk
    mask_kw = dict(scale=1.0 / (D ** 0.5), tq=tq, tk=tk, causal=causal,
                   block_causal=block_causal, ell=ell, nh=H, compute=compute)

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, rep, tq, D), lambda b, i, j, lv: (b, 0, i, 0)),
            pl.BlockSpec((1, tk, D), lambda b, i, j, lv: (b, j, 0)),
            pl.BlockSpec((1, tk, D), lambda b, i, j, lv: (b, j, 0)),
            pl.BlockSpec((1, 1, tk), lambda b, i, j, lv: (b // H, 0, j)),
            pl.BlockSpec((1, rep, tq, D), lambda b, i, j, lv: (b, 0, i, 0)),
            pl.BlockSpec((1, rep, tq), lambda b, i, j, lv: (b, 0, i)),
            pl.BlockSpec((1, rep, tq), lambda b, i, j, lv: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, rep, tq, D),
                               lambda b, i, j, lv: (b, 0, i, 0)),
        scratch_shapes=[pltpu.VMEM((rep * tq, D), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_k=n_k, **mask_kw),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((BH, rep, N, D), q.dtype),
        name="bsa_flash_dq",
        interpret=interpret,
    )(live, q, k, v, key_bias[:, None], do, lse, delta)

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, rep, tq, D), lambda b, j, i, lv: (b, 0, i, 0)),
            pl.BlockSpec((1, tk, D), lambda b, j, i, lv: (b, j, 0)),
            pl.BlockSpec((1, tk, D), lambda b, j, i, lv: (b, j, 0)),
            pl.BlockSpec((1, 1, tk), lambda b, j, i, lv: (b // H, 0, j)),
            pl.BlockSpec((1, rep, tq, D), lambda b, j, i, lv: (b, 0, i, 0)),
            pl.BlockSpec((1, rep, tq), lambda b, j, i, lv: (b, 0, i)),
            pl.BlockSpec((1, rep, tq), lambda b, j, i, lv: (b, 0, i)),
        ],
        out_specs=(pl.BlockSpec((1, tk, D), lambda b, j, i, lv: (b, j, 0)),
                   pl.BlockSpec((1, tk, D), lambda b, j, i, lv: (b, j, 0))),
        scratch_shapes=[pltpu.VMEM((tk, D), jnp.float32),
                        pltpu.VMEM((tk, D), jnp.float32)],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, **mask_kw),
        grid_spec=dkv_spec,
        out_shape=(jax.ShapeDtypeStruct((BH, L, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, L, D), v.dtype)),
        name="bsa_flash_dkv",
        interpret=interpret,
    )(live, q, k, v, key_bias[:, None], do, lse, delta)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _make_vjp(n_heads: int, tq: int, tk: int, causal: bool, block_causal: bool,
              ell: int, interpret: bool, compute: str):
    kw = dict(n_heads=n_heads, tq=tq, tk=tk, causal=causal,
              block_causal=block_causal, ell=ell, interpret=interpret,
              compute=compute)

    @jax.custom_vjp
    def attend(q, k, v, key_bias, live):
        return _fwd_call(q, k, v, key_bias, live, **kw)[0]

    def attend_fwd(q, k, v, key_bias, live):
        o, lse = _fwd_call(q, k, v, key_bias, live, **kw)
        return o, (q, k, v, key_bias, live, o, lse)

    def attend_bwd(res, do):
        q, k, v, key_bias, live, o, lse = res
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        dq, dk, dv = _bwd_calls(q, k, v, key_bias, live, do, lse, delta, **kw)
        return dq, dk, dv, None, None                      # bias/liveness: no grad

    attend.defvjp(attend_fwd, attend_bwd)
    return attend


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "tq", "tk", "causal", "block_causal", "ell", "interpret",
    "compute"))
def flash_attention_kernel_call(q, k, v, key_bias, live=None, *, n_heads: int,
                                tq: int = 256, tk: int = 256,
                                causal: bool = False, block_causal: bool = False,
                                ell: int = 1, interpret: bool | None = None,
                                compute: str | None = None):
    """q: (B·Hkv, rep, N, D) grouped queries; k, v: (B·Hkv, L, D) — one K/V
    stream per KV head shared by its rep query heads; key_bias: (B, L) fp32
    additive; ``live``: optional (B, N/tq, L/tk) int32 tile-liveness map
    (``occupancy.flash_live_map``; None = all live); ``n_heads`` is the KV
    head count Hkv.  ``tq`` must divide N and ``tk`` divide L
    (``kernels/ops.py`` pads both axes to guarantee this).  ``compute`` is
    the canonical matmul-operand dtype name (None resolves from q.dtype —
    see ``common.resolve_compute_dtype``; callers that toggle REPRO_FP8
    between calls should pass it explicitly, since this wrapper is jitted).
    Returns (B·Hkv, rep, N, D).  Differentiable in q, k, v."""
    BH, rep, N, D = q.shape
    L = k.shape[1]
    tq = min(tq, N)
    tk = min(tk, L)
    if N % tq or L % tk:
        # a real error, not an assert: under python -O a silently truncated
        # grid would leave the tail query rows of the output unwritten
        raise ValueError(f"tiles must divide the (padded) axes: N={N} tq={tq},"
                         f" L={L} tk={tk} — kernels/ops.flash_attention pads;"
                         " direct callers must pass dividing tiles")
    if interpret is None:
        interpret = should_interpret()
    if compute is None:
        compute = resolve_compute_dtype(q.dtype)
    if live is None:
        live = jnp.ones((key_bias.shape[0], N // tq, L // tk), jnp.int32)
    if interpret and BH > 1:
        # CPU fallback: per-slice grids keep the interpreter linear in B·Hkv
        bias_bh = jnp.repeat(key_bias, n_heads, axis=0)
        live_bh = jnp.repeat(live, n_heads, axis=0)
        return interpret_batch_map(
            _make_vjp(1, tq, tk, causal, block_causal, ell, True, compute),
            q, k, v, bias_bh, live_bh)
    return _make_vjp(n_heads, tq, tk, causal, block_causal, ell, interpret,
                     compute)(q, k, v, key_bias, live)
