"""Packed-varlen flash-attention Pallas kernel (the cu_seqlens idiom).

The bucket-padded layout gives every sample a full (B, L) slot, so small
samples burn padding FLOPs.  Here all samples share ONE packed axis of
length T = Σ paddedᵢ and an ``offsets`` array marks the boundaries — the
layout NSA-style varlen kernels use (flash-linear-attention's
``USE_OFFSETS`` path): per-sample start/end are resolved *inside* the
kernel, so one compiled shape serves any size mix and total work scales
with the real token count.

Two mechanisms enforce sample isolation:

  * **Within-tile segment masking** — per-position int32 segment ids
    (``numerics.segment_ids_from_offsets``) for queries and keys ride in as
    tensor operands; a tile that straddles a sample boundary masks the
    cross-sample (q, k) pairs to ``NEG_INF`` in logit space, exactly like
    key-padding masking.
  * **Tile skipping** — per-tile segment RANGES (min/max segment id, shape
    ``(2, n_tiles)`` int32) ride in as SCALAR-PREFETCH operands
    (``PrefetchScalarGridSpec``).  A (q-tile, k-tile) grid cell whose ranges
    don't overlap is entirely cross-sample: ``pl.when(live)`` skips its
    matmuls, which is exact — a fully-masked tile contributes nothing to
    the streaming softmax statistics.  This is what kills the O(T²)
    padding work: for S similar samples only ~1/S of the grid is live.

Layout matches ``kernels/flash.py`` (GQA-native): the packed batch is B=1,
the grid iterates KV heads — (Hkv, nQ, nK), K innermost — queries arrive
``(Hkv, rep, T, D)``, K/V ``(Hkv, L, D)``, key bias ``(1, L)``, segment ids
``(1, T)`` / ``(1, L)``.  Capacity padding (rows at/after ``offsets[-1]``)
carries segment id S, which matches no real sample, so padded queries and
keys are mutually invisible to real ones by the same equality test.

Differentiable: fused custom_vjp with FlashAttention-style recomputation —
dQ on the forward grid, dK/dV on the transposed grid (Q innermost), both
with the same live-tile skip.  Segment ids, ranges and the key bias are
masks: no gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (NEG_INF, lse_finalize, mma_dtype,
                                  p_from_lse, resolve_compute_dtype,
                                  rows_to_column, should_interpret)
from repro.kernels.occupancy import ranges_overlap

__all__ = ["flash_attention_varlen_kernel_call"]


def _seg_mask(s, qs, ks, *, rep):
    """Mask cross-sample (q, k) pairs of one tile to NEG_INF.

    ``qs``: (1, tq) query segment ids; ``ks``: (1, tk) key segment ids.
    Row r of the fused (rep·tq)-row group tile is query position ``r % tq``
    (rep-major layout), so all rep heads see the same mask row."""
    qsr = rows_to_column(jnp.broadcast_to(qs, (rep, qs.shape[1])))
    return jnp.where(qsr == ks, s, NEG_INF)


def _fwd_kernel(qrng, krng, q_ref, k_ref, v_ref, kbias_ref, qs_ref, ks_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, n_k: int, tq: int, tk: int, compute: str):
    i = pl.program_id(1)
    j = pl.program_id(2)
    rep, _, D = q_ref.shape[1:]
    rows = rep * tq
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ranges_overlap(qrng, krng, i, j))
    def _step():
        q = q_ref[0].astype(sdt).reshape(rows, D)          # (rep·Tq, D)
        k = k_ref[0].astype(sdt)                           # (Tk, D)
        v = v_ref[0].astype(adt)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + kbias_ref[0]                               # (Tk,) key-validity bias
        s = _seg_mask(s, qs_ref[...], ks_ref[...], rep=rep)

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


def _dq_kernel(qrng, krng, q_ref, k_ref, v_ref, kbias_ref, qs_ref, ks_ref,
               do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
               scale: float, n_k: int, tq: int, tk: int, compute: str):
    i = pl.program_id(1)
    j = pl.program_id(2)
    rep, _, D = q_ref.shape[1:]
    rows = rep * tq
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(ranges_overlap(qrng, krng, i, j))
    def _step():
        q = q_ref[0].astype(sdt).reshape(rows, D)
        k = k_ref[0].astype(sdt)
        v = v_ref[0].astype(adt)
        do = do_ref[0].astype(adt).reshape(rows, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + kbias_ref[0]
        s = _seg_mask(s, qs_ref[...], ks_ref[...], rep=rep)
        p = p_from_lse(s, rows_to_column(lse_ref[0]))     # (rep·Tq, Tk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - rows_to_column(delta_ref[0])) * scale
        dq_scr[...] += jax.lax.dot_general(ds.astype(adt), k.astype(adt),
                                           (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(j == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].reshape(rep, tq, D).astype(dq_ref.dtype)


def _dkv_kernel(qrng, krng, q_ref, k_ref, v_ref, kbias_ref, qs_ref, ks_ref,
                do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale: float, n_q: int, tq: int, tk: int, compute: str):
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

    @pl.when(ranges_overlap(qrng, krng, i, j))
    def _step():
        q = q_ref[0].astype(sdt).reshape(rows, D)
        k = k_ref[0].astype(sdt)
        v = v_ref[0].astype(adt)
        do = do_ref[0].astype(adt).reshape(rows, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + kbias_ref[0]
        s = _seg_mask(s, qs_ref[...], ks_ref[...], rep=rep)
        p = p_from_lse(s, rows_to_column(lse_ref[0]))
        # (0,)-axis contraction: the GQA group's dK/dV accumulate in-matmul
        dv_scr[...] += jax.lax.dot_general(p.astype(adt), do,
                                           (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - rows_to_column(delta_ref[0])) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(adt), q_ref[0].astype(adt).reshape(rows, D),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _fwd_call(q, k, v, key_bias, qseg, kseg, qrng, krng, *, tq, tk,
              interpret, compute):
    BH, rep, N, D = q.shape
    L = k.shape[1]
    n_k = L // tk
    kern = functools.partial(_fwd_kernel, scale=1.0 / (D ** 0.5), n_k=n_k,
                             tq=tq, tk=tk, compute=compute)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, N // tq, n_k),
        in_specs=[
            pl.BlockSpec((1, rep, tq, D), lambda b, i, j, qr, kr: (b, 0, i, 0)),
            pl.BlockSpec((1, tk, D), lambda b, i, j, qr, kr: (b, j, 0)),
            pl.BlockSpec((1, tk, D), lambda b, i, j, qr, kr: (b, j, 0)),
            pl.BlockSpec((1, tk), lambda b, i, j, qr, kr: (0, j)),
            pl.BlockSpec((1, tq), lambda b, i, j, qr, kr: (0, i)),
            pl.BlockSpec((1, tk), lambda b, i, j, qr, kr: (0, j)),
        ],
        out_specs=(
            pl.BlockSpec((1, rep, tq, D), lambda b, i, j, qr, kr: (b, 0, i, 0)),
            pl.BlockSpec((1, rep, tq), lambda b, i, j, qr, kr: (b, 0, i)),
        ),
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
        name="bsa_varlen_fwd",
        interpret=interpret,
    )(qrng, krng, q, k, v, key_bias, qseg, kseg)


def _bwd_calls(q, k, v, key_bias, qseg, kseg, qrng, krng, do, lse, delta, *,
               tq, tk, interpret, compute):
    BH, rep, N, D = q.shape
    L = k.shape[1]
    n_q, n_k = N // tq, L // tk
    kw = dict(scale=1.0 / (D ** 0.5), tq=tq, tk=tk, compute=compute)

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, rep, tq, D), lambda b, i, j, qr, kr: (b, 0, i, 0)),
            pl.BlockSpec((1, tk, D), lambda b, i, j, qr, kr: (b, j, 0)),
            pl.BlockSpec((1, tk, D), lambda b, i, j, qr, kr: (b, j, 0)),
            pl.BlockSpec((1, tk), lambda b, i, j, qr, kr: (0, j)),
            pl.BlockSpec((1, tq), lambda b, i, j, qr, kr: (0, i)),
            pl.BlockSpec((1, tk), lambda b, i, j, qr, kr: (0, j)),
            pl.BlockSpec((1, rep, tq, D), lambda b, i, j, qr, kr: (b, 0, i, 0)),
            pl.BlockSpec((1, rep, tq), lambda b, i, j, qr, kr: (b, 0, i)),
            pl.BlockSpec((1, rep, tq), lambda b, i, j, qr, kr: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, rep, tq, D),
                               lambda b, i, j, qr, kr: (b, 0, i, 0)),
        scratch_shapes=[pltpu.VMEM((rep * tq, D), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_k=n_k, **kw),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((BH, rep, N, D), q.dtype),
        name="bsa_varlen_dq",
        interpret=interpret,
    )(qrng, krng, q, k, v, key_bias, qseg, kseg, do, lse, delta)

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, rep, tq, D), lambda b, j, i, qr, kr: (b, 0, i, 0)),
            pl.BlockSpec((1, tk, D), lambda b, j, i, qr, kr: (b, j, 0)),
            pl.BlockSpec((1, tk, D), lambda b, j, i, qr, kr: (b, j, 0)),
            pl.BlockSpec((1, tk), lambda b, j, i, qr, kr: (0, j)),
            pl.BlockSpec((1, tq), lambda b, j, i, qr, kr: (0, i)),
            pl.BlockSpec((1, tk), lambda b, j, i, qr, kr: (0, j)),
            pl.BlockSpec((1, rep, tq, D), lambda b, j, i, qr, kr: (b, 0, i, 0)),
            pl.BlockSpec((1, rep, tq), lambda b, j, i, qr, kr: (b, 0, i)),
            pl.BlockSpec((1, rep, tq), lambda b, j, i, qr, kr: (b, 0, i)),
        ],
        out_specs=(pl.BlockSpec((1, tk, D), lambda b, j, i, qr, kr: (b, j, 0)),
                   pl.BlockSpec((1, tk, D), lambda b, j, i, qr, kr: (b, j, 0))),
        scratch_shapes=[pltpu.VMEM((tk, D), jnp.float32),
                        pltpu.VMEM((tk, D), jnp.float32)],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, **kw),
        grid_spec=dkv_spec,
        out_shape=(jax.ShapeDtypeStruct((BH, L, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, L, D), v.dtype)),
        name="bsa_varlen_dkv",
        interpret=interpret,
    )(qrng, krng, q, k, v, key_bias, qseg, kseg, do, lse, delta)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _make_vjp(tq: int, tk: int, interpret: bool, compute: str):
    kw = dict(tq=tq, tk=tk, interpret=interpret, compute=compute)

    @jax.custom_vjp
    def attend(q, k, v, key_bias, qseg, kseg, qrng, krng):
        return _fwd_call(q, k, v, key_bias, qseg, kseg, qrng, krng, **kw)[0]

    def attend_fwd(q, k, v, key_bias, qseg, kseg, qrng, krng):
        o, lse = _fwd_call(q, k, v, key_bias, qseg, kseg, qrng, krng, **kw)
        return o, (q, k, v, key_bias, qseg, kseg, qrng, krng, o, lse)

    def attend_bwd(res, do):
        q, k, v, key_bias, qseg, kseg, qrng, krng, o, lse = res
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        dq, dk, dv = _bwd_calls(q, k, v, key_bias, qseg, kseg, qrng, krng,
                                do, lse, delta, **kw)
        return dq, dk, dv, None, None, None, None, None    # masks/ids: no grad

    attend.defvjp(attend_fwd, attend_bwd)
    return attend


@functools.partial(jax.jit, static_argnames=("tq", "tk", "interpret",
                                             "compute"))
def flash_attention_varlen_kernel_call(q, k, v, key_bias, qseg, kseg,
                                       qrng, krng, *, tq: int = 256,
                                       tk: int = 256,
                                       interpret: bool | None = None,
                                       compute: str | None = None):
    """Packed-varlen flash attention over one concatenated sample axis.

    q: (Hkv, rep, T, D) grouped queries; k, v: (Hkv, L, D); key_bias: (1, L)
    fp32 additive (padding/validity); qseg: (1, T) / kseg: (1, L) int32
    per-position segment ids; qrng: (2, n_q_tiles) / krng: (2, n_k_tiles)
    int32 per-tile [min, max] segment ranges (scalar-prefetched for tile
    skipping).  ``tq`` must divide T and ``tk`` divide L
    (``kernels/ops.flash_attention_varlen`` pads and derives the seg
    operands — direct callers rarely want this entry point).
    Returns (Hkv, rep, T, D).  Differentiable in q, k, v."""
    BH, rep, N, D = q.shape
    L = k.shape[1]
    tq = min(tq, N)
    tk = min(tk, L)
    if N % tq or L % tk:
        raise ValueError(f"tiles must divide the (padded) axes: T={N} tq={tq},"
                         f" L={L} tk={tk} — kernels/ops.flash_attention_varlen"
                         " pads; direct callers must pass dividing tiles")
    if interpret is None:
        interpret = should_interpret()
    if compute is None:
        compute = resolve_compute_dtype(q.dtype)
    if interpret and BH > 1:
        # CPU fallback: per-KV-head grids keep the interpreter linear in Hkv.
        # Bias/seg/range operands are shared across heads — close over them
        # and map only q/k/v (they are also the only differentiable inputs).
        f = _make_vjp(tq, tk, True, compute)

        def one_head(t):
            qh, kh, vh = t
            return f(qh[None], kh[None], vh[None], key_bias, qseg, kseg,
                     qrng, krng)[0]

        return jax.lax.map(one_head, (q, k, v))
    return _make_vjp(tq, tk, interpret, compute)(q, k, v, key_bias, qseg,
                                                 kseg, qrng, krng)
