"""Ball-Tree Attention Pallas kernel (block-diagonal fused attention).

The ball IS the tile: with ball size m ≤ 512 and head_dim ≤ 128, one ball's
K/V (m×D) fits in VMEM whole, so the kernel is a single-pass fused
softmax-attention per (batch·KV-head, ball) grid cell — no streaming, no
running-max bookkeeping.

GQA-NATIVE: the grid iterates KV heads, not query heads.  Queries arrive as
(B·Hkv, rep, N, D) — the ``rep = Hq/Hkv`` query heads of one GQA group ride
the same grid cell as their shared K/V tile, collapsed into the matmul row
dimension: the two matmuls are (rep·m, D)×(D, m) and (rep·m, m)×(m, D).
One K/V fetch HBM→VMEM serves the whole group (NSA's shared-KV-fetch
speedup), and the extra query rows FEED the MXU rather than re-fetching.
MXU-aligned: rep·m is a multiple of 8 (sublane) and D ∈ {64, 128} (lane).

VMEM budget per grid step (m=256, rep=4, D=128, bf16 in / fp32 logits):
  q: 256 KiB; k,v: 2·64 KiB; logits+p: 2·1024·256·4 B = 2 MiB;
  out: 256 KiB  →  < 3 MiB of the ~16 MiB VMEM.

TILE-OCCUPANCY SKIPPING (``kernels/occupancy.py``): a per-ball
any-valid-key verdict (B, n_b) int32 rides in as a SCALAR-PREFETCH operand.
An all-padding ball (the tail balls of short samples in a ragged batch)
skips both matmuls via ``pl.when`` and writes the exact dead-row answer
directly — zeros with lse = LSE_EMPTY forward, zero dQ/dK/dV backward —
matching the jnp oracle bit-for-bit.

PRECISION CONTRACT (``common.resolve_compute_dtype``): operand tiles cast
to the compute dtype (bf16 in → bf16 through QK^T and PV, fp8 QK^T under
REPRO_FP8=1) while every ``dot_general`` accumulates fp32 and the softmax
statistics stay fp32.

Differentiable: forward additionally emits the per-row logsumexp
(B·Hkv, rep, N); the backward is a single-pass per-ball kernel (the
ball-is-the-tile layout means dQ, dK, dV of a ball depend only on that ball)
that recomputes p = exp(s − lse) and produces all three gradients in one
grid sweep — dK/dV accumulate over the group's rep query heads inside the
(rep·m)-row matmul itself, so no cross-cell reduction is needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (LSE_EMPTY, NEG_INF, interpret_batch_map,
                                  lse_finalize, mma_dtype, p_from_lse,
                                  resolve_compute_dtype, rows_to_column,
                                  should_interpret)
from repro.kernels.occupancy import key_tile_live

__all__ = ["ball_attention_kernel_call"]


def _fwd_kernel(live_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                scale: float, nh: int, compute: str):
    b = pl.program_id(0)
    i = pl.program_id(1)
    rep, m, D = q_ref.shape[1:]
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))

    @pl.when(live_ref[b // nh, i] != 0)
    def _live_ball():
        q = q_ref[0].astype(sdt).reshape(rep * m, D)      # group rows fused
        k = k_ref[0].astype(sdt)                          # (m, D) one fetch/group
        v = v_ref[0].astype(adt)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0]                               # (rep·m, m) + (1, m)
        mx = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), NEG_INF / 2)
        p = jnp.exp(s - mx)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        l = jnp.sum(p, axis=-1, keepdims=True)
        denom = jnp.maximum(l, 1e-20)
        o = jax.lax.dot_general((p / denom).astype(adt), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[0] = o.reshape(rep, m, D).astype(o_ref.dtype)
        lse_ref[0] = lse_finalize(mx, l)[:, 0].reshape(rep, m)

    @pl.when(live_ref[b // nh, i] == 0)
    def _dead_ball():                                     # all keys masked:
        o_ref[0] = jnp.zeros_like(o_ref[0])               # exact oracle zeros,
        lse_ref[0] = jnp.full_like(lse_ref[0], LSE_EMPTY)  # p ≡ 0 in backward


def _bwd_kernel(live_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, *, scale: float, nh: int,
                compute: str):
    b = pl.program_id(0)
    i = pl.program_id(1)
    rep, m, D = q_ref.shape[1:]
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))

    @pl.when(live_ref[b // nh, i] != 0)
    def _live_ball():
        q = q_ref[0].astype(sdt).reshape(rep * m, D)
        k = k_ref[0].astype(sdt)
        v = v_ref[0].astype(adt)
        do = do_ref[0].astype(adt).reshape(rep * m, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0]
        p = p_from_lse(s, rows_to_column(lse_ref[0]))  # (rep·m, m)
        # dK/dV: one matmul sums over the rep·m group rows — the GQA group's
        # gradient accumulation is the contraction itself
        dv = jax.lax.dot_general(p.astype(adt), do, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - rows_to_column(delta_ref[0])) * scale
        dq = jax.lax.dot_general(ds.astype(adt), k.astype(adt),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dk = jax.lax.dot_general(ds.astype(adt),
                                 q_ref[0].astype(adt).reshape(rep * m, D),
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dq_ref[0] = dq.reshape(rep, m, D).astype(dq_ref.dtype)
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(live_ref[b // nh, i] == 0)
    def _dead_ball():                                     # p ≡ 0 → zero grads
        dq_ref[0] = jnp.zeros_like(dq_ref[0])
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])


def _fwd_call(q, k, v, key_bias, ball_live, *, ball_size, n_heads, interpret,
              compute):
    BH, rep, N, D = q.shape
    m = ball_size
    assert N % m == 0
    H = n_heads                                           # KV heads
    qblk = pl.BlockSpec((1, rep, m, D), lambda b, i, lv: (b, 0, i, 0))
    kvblk = pl.BlockSpec((1, m, D), lambda b, i, lv: (b, i, 0))
    bias_blk = pl.BlockSpec((1, 1, m), lambda b, i, lv: (b // H, 0, i))
    lse_blk = pl.BlockSpec((1, rep, m), lambda b, i, lv: (b, 0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, N // m),
        in_specs=[qblk, kvblk, kvblk, bias_blk],
        out_specs=(qblk, lse_blk),
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / (D ** 0.5), nh=H,
                          compute=compute),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((BH, rep, N, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, rep, N), jnp.float32)),
        name="bsa_ball_fwd",
        interpret=interpret,
    )(ball_live, q, k, v, key_bias[:, None])


def _bwd_call(q, k, v, key_bias, ball_live, do, lse, delta, *, ball_size,
              n_heads, interpret, compute):
    BH, rep, N, D = q.shape
    m = ball_size
    H = n_heads
    qblk = pl.BlockSpec((1, rep, m, D), lambda b, i, lv: (b, 0, i, 0))
    kvblk = pl.BlockSpec((1, m, D), lambda b, i, lv: (b, i, 0))
    bias_blk = pl.BlockSpec((1, 1, m), lambda b, i, lv: (b // H, 0, i))
    row_blk = pl.BlockSpec((1, rep, m), lambda b, i, lv: (b, 0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, N // m),
        in_specs=[qblk, kvblk, kvblk, bias_blk, qblk, row_blk, row_blk],
        out_specs=(qblk, kvblk, kvblk),
    )
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=1.0 / (D ** 0.5), nh=H,
                          compute=compute),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((BH, rep, N, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, N, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, N, D), v.dtype)),
        name="bsa_ball_bwd",
        interpret=interpret,
    )(ball_live, q, k, v, key_bias[:, None], do, lse, delta)


@functools.lru_cache(maxsize=None)
def _make_vjp(ball_size: int, n_heads: int, interpret: bool, compute: str):
    kw = dict(ball_size=ball_size, n_heads=n_heads, interpret=interpret,
              compute=compute)

    @jax.custom_vjp
    def attend(q, k, v, key_bias, ball_live):
        return _fwd_call(q, k, v, key_bias, ball_live, **kw)[0]

    def attend_fwd(q, k, v, key_bias, ball_live):
        o, lse = _fwd_call(q, k, v, key_bias, ball_live, **kw)
        return o, (q, k, v, key_bias, ball_live, o, lse)

    def attend_bwd(res, do):
        q, k, v, key_bias, ball_live, o, lse = res
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        dq, dk, dv = _bwd_call(q, k, v, key_bias, ball_live, do, lse, delta,
                               **kw)
        return dq, dk, dv, None, None                     # bias/live: no grad

    attend.defvjp(attend_fwd, attend_bwd)
    return attend


@functools.partial(jax.jit, static_argnames=("ball_size", "n_heads",
                                             "interpret", "compute"))
def ball_attention_kernel_call(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                               key_bias: jnp.ndarray, *, ball_size: int,
                               n_heads: int, interpret: bool | None = None,
                               compute: str | None = None):
    """q: (B·Hkv, rep, N, D) grouped queries; k, v: (B·Hkv, N, D) — ONE K/V
    stream per KV head, shared by its ``rep`` query heads; key_bias: (B, N)
    fp32 additive (0 / NEG_INF); ``n_heads`` is the KV head count Hkv.
    ``compute``: canonical matmul-operand dtype name (None resolves from
    q.dtype).  Per-ball liveness is derived from ``key_bias`` and
    scalar-prefetched: all-padding balls skip both matmuls exactly.
    Returns (B·Hkv, rep, N, D).  Differentiable in q, k, v."""
    if interpret is None:
        interpret = should_interpret()
    if compute is None:
        compute = resolve_compute_dtype(q.dtype)
    ball_live = key_tile_live(key_bias, ball_size).astype(jnp.int32)  # (B, n_b)
    if interpret and q.shape[0] > 1:
        # CPU fallback: per-slice grids keep the interpreter linear in B·Hkv
        bias_bh = jnp.repeat(key_bias, n_heads, axis=0)
        live_bh = jnp.repeat(ball_live, n_heads, axis=0)
        return interpret_batch_map(_make_vjp(ball_size, 1, True, compute),
                                   q, k, v, bias_bh, live_bh)
    return _make_vjp(ball_size, n_heads, interpret, compute)(
        q, k, v, key_bias, ball_live)
