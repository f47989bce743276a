"""Group-selected sparse attention Pallas kernel (the NSA/BSA hot path).

This is the TPU-native realization of the kernel the paper leaves as future
work ("we do not implement a Triton kernel for efficient selection").  The
per-group top-k block indices are **scalar-prefetched** to SMEM
(``pltpu.PrefetchScalarGridSpec``) as one flat array, and every selected
ℓ-sized KV block is read at its id.  The M (rows) dimension of every matmul
is the whole query group (g positions × rep GQA heads), which is what keeps
the MXU fed despite tiny ℓ = 8 blocks — the hardware-alignment rationale of
NSA group fetch.  Two grid schedules run this same attention; the input's
shape picks one (``selection_schedule``):

* **resident** — grid (B, Hkv, ⌈G / Gc⌉).  A (b, h)'s whole K/V block slab
  (NB, ℓ, D) and its key-padding bias are one block whose index depends
  only on (b, h), so they are DMA'd into VMEM once per (b, h) and stay
  there across the chunk axis.  One grid step loops over a chunk of Gc
  query groups; each group reads its k* selected blocks from the slab at
  the prefetched ids and runs ONE softmax over its k*·ℓ keys, with keys on
  the sublane axis (scores are (k*·ℓ, M), the per-row statistics (1, M)
  rows).  The backward runs on the same grid with the chunk axis
  sequential: dK and dV are (NB, ℓ, D) fp32 output blocks indexed by
  (b, h) alone, accumulated in VMEM with ``+=`` at each selected id in
  order (a block several groups select sums correctly), and written back
  once per (b, h).  dQ of a group is complete inside its own iteration.
* **blocked** — grid (B, Hkv, G, k*), one selected block per grid step:
  the BlockSpec index maps DMA exactly that ℓ-block HBM→VMEM, and
  flash-style running-softmax scratch carries the accumulation across a
  group's k* blocks.  The backward writes per-selection dK/dV tiles to a
  (B, Hkv, G, k*, ℓ, D) staging buffer that the VJP wrapper scatter-adds
  back through the block ids.

**The rule.**  Resident engages in each direction when its VMEM footprint
— the slabs, the chunk's row blocks and the loop's live temporaries, every
block counted at its padded (sublane, 128-lane) tile size — fits
``_RESIDENT_VMEM_BYTES`` (``_resident_buffers``): with the slabs
double-buffered if that fits, else single-buffered.  Slabs that do not fit
(long LM contexts, very large clouds) take the blocked grid.  Per-step
overhead, not work, bounds the blocked grid (each step moves ~2 KB); the
resident one pays a few hundred grid steps per launch.  The launch runs
under ``jax.named_scope("resident")`` or ``("blocked")``, so a trace says
which schedule each launch took.

Invalid selections are encoded as index −1.  The blocked grid clamps them
to block 0 (a harmless fetch) and skips their matmuls via ``pl.when`` in
BOTH directions, writing exact-zero dK/dV staging tiles; the resident loop
reads clamped block 0 and adds NEG_INF to its keys' logits, so p ≡ 0 there
and its dK/dV contributions are exact zeros.  The selection front-ends
additionally invalidate every selection of an all-padding query group
(``occupancy.invalidate_dead_groups``); such a group emits exact zeros and
``LSE_EMPTY``.

PRECISION CONTRACT (``common.resolve_compute_dtype``): operand tiles cast
to the compute dtype (bf16 in → bf16 through QK^T and PV, fp8 QK^T under
REPRO_FP8=1) while every ``dot_general`` accumulates fp32 and the softmax
statistics stay fp32.  The two schedules differ only in the order of float
sums.

Differentiable: the forward emits per-row logsumexp; the backward
recomputes p = exp(s − lse) per selected block (or group) and accumulates
dQ over a group's k* blocks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (NEG_INF, interpret_batch_map, lse_finalize,
                                  mma_dtype, p_from_lse, resolve_compute_dtype,
                                  should_interpret)

__all__ = ["selection_attention_kernel_call", "selection_schedule"]


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


# ---------------------------------------------------------------------------
# resident schedule: one (b, h)'s K/V slab in VMEM, Gc query groups per step
# ---------------------------------------------------------------------------

def _gather_group(idx_ref, k_ref, v_ref, bias_ref, b, h, g, dims):
    """A group's k* selected blocks from the resident slabs, keys stacked
    on the sublane axis: K, V (k*·ℓ, D) fp32, bias (k*·ℓ, 1) with NEG_INF
    on every key of an invalid (−1) selection, and the k* block ids read
    (an invalid one clamped to 0).  Stacking in fp32 keeps the ℓ-row pieces
    tile-aligned whatever the storage dtype."""
    ks, vs, bs, blks = [], [], [], []
    for j in range(dims[3]):
        sel = _sel_id(idx_ref, b, h, g, j, dims)
        blk = jnp.maximum(sel, 0)
        ks.append(k_ref[0, 0, blk].astype(jnp.float32))
        vs.append(v_ref[0, 0, blk].astype(jnp.float32))
        bs.append(bias_ref[0, blk] + jnp.where(sel >= 0, 0.0, NEG_INF))
        blks.append(blk)
    cat = functools.partial(jnp.concatenate, axis=0)
    return cat(ks), cat(vs), cat(bs), blks


def _chunk_groups(chunk: int, unroll: int, dims: tuple, body):
    """Run ``body(i, r, g, live)`` for the chunk's slots i = 0 … Gc−1,
    ``unroll`` groups per loop iteration so the scheduler can overlap them.
    Slot i writes row i and reads row r of group g.  A slot past the last
    group (a partial last chunk; ``live`` False) re-reads the chunk's last
    group, so it never touches ids or rows outside the arrays; its row
    writes fall outside the array and are dropped."""
    c = pl.program_id(2)
    last = jnp.minimum(chunk, dims[2] - c * chunk) - 1

    def step(t, carry):
        for u in range(unroll):
            i = t * unroll + u
            r = jnp.minimum(i, last)
            body(i, r, c * chunk + r, i <= last)
        return carry

    jax.lax.fori_loop(0, chunk // unroll, step, 0)


def _resident_fwd_kernel(idx_ref, q_ref, k_ref, v_ref, bias_ref,
                         o_ref, lse_ref, *, scale: float, dims: tuple,
                         chunk: int, unroll: int, compute: str):
    b = pl.program_id(0)
    h = pl.program_id(1)
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))

    def group(i, r, g, live):
        kg, vg, bias, _ = _gather_group(idx_ref, k_ref, v_ref, bias_ref,
                                        b, h, g, dims)
        q = q_ref[0, 0, r].astype(sdt)                     # (M, D)
        s = jax.lax.dot_general(kg.astype(sdt), q, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias                                       # (k*·ℓ, M)
        m_safe = jnp.maximum(jnp.max(s, axis=0, keepdims=True), NEG_INF / 2)
        p = jnp.exp(s - m_safe)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        l = jnp.sum(p, axis=0, keepdims=True)              # (1, M)
        o = jax.lax.dot_general(p.astype(adt), vg.astype(adt),
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        l_col = l.reshape(-1, 1)
        o = jnp.where(l_col > 0.0, o / jnp.maximum(l_col, 1e-20), 0.0)
        o_ref[0, 0, i] = o.astype(o_ref.dtype)             # all-invalid → 0
        lse_ref[0, 0, i] = lse_finalize(m_safe, l)

    _chunk_groups(chunk, unroll, dims, group)


def _resident_bwd_kernel(idx_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                         lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, *,
                         scale: float, dims: tuple, chunk: int, unroll: int,
                         compute: str):
    b = pl.program_id(0)
    h = pl.program_id(1)
    sdt = jnp.dtype(compute)
    adt = jnp.dtype(mma_dtype(compute))
    ell = k_ref.shape[3]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def group(i, r, g, live):
        kg, vg, bias, blks = _gather_group(idx_ref, k_ref, v_ref, bias_ref,
                                           b, h, g, dims)
        kg = kg.astype(sdt)
        q = q_ref[0, 0, r]
        do = do_ref[0, 0, r].astype(adt)
        s = jax.lax.dot_general(kg, q.astype(sdt), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias                                       # (k*·ℓ, M)
        # a slot past the last group contributes nothing to dK/dV
        p = p_from_lse(s, lse_ref[0, 0, r]) * live.astype(jnp.float32)
        dv = jax.lax.dot_general(p.astype(adt), do, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(vg.astype(adt), do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, r]) * scale
        dk = jax.lax.dot_general(ds.astype(adt), q.astype(adt),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dq_ref[0, 0, i] = jax.lax.dot_general(
            ds.astype(adt), kg.astype(adt), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)
        # invalid selections hit clamped block 0 with exact-zero rows (p ≡ 0)
        for j, blk in enumerate(blks):
            dk_ref[0, 0, blk] += dk[j * ell:(j + 1) * ell]
            dv_ref[0, 0, blk] += dv[j * ell:(j + 1) * ell]

    _chunk_groups(chunk, unroll, dims, group)


def _resident_specs(q, kb, buffers: int):
    """Grid, chunk size, unroll and the shared BlockSpecs of both resident
    calls: row blocks of Gc groups, slabs indexed by (b, h) alone."""
    B, Hkv, G, M, D = q.shape
    nb, ell = kb.shape[2], kb.shape[3]
    chunk, unroll = _chunking(G)
    mode = {} if buffers == 2 else dict(pipeline_mode=pl.Buffered(1))
    rows = lambda b, h, c, idx_ref: (b, h, c, 0, 0)
    slab = pl.BlockSpec((1, 1, nb, ell, D),
                        lambda b, h, c, idx_ref: (b, h, 0, 0, 0), **mode)
    bias = pl.BlockSpec((1, nb, ell, 1),
                        lambda b, h, c, idx_ref: (b, 0, 0, 0), **mode)
    return dict(
        grid=(B, Hkv, pl.cdiv(G, chunk)), chunk=chunk, unroll=unroll,
        row=pl.BlockSpec((1, 1, chunk, M, D), rows),
        stat=pl.BlockSpec((1, 1, chunk, 1, M), rows), slab=slab, bias=bias)


def _resident_fwd_call(q, kb, vb, idx, tok_bias, *, interpret, compute,
                       buffers):
    B, Hkv, G, M, D = q.shape
    sp = _resident_specs(q, kb, buffers)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=sp["grid"],
        in_specs=[sp["row"], sp["slab"], sp["slab"], sp["bias"]],
        out_specs=(sp["row"], sp["stat"]))
    return pl.pallas_call(
        functools.partial(_resident_fwd_kernel, scale=1.0 / (D ** 0.5),
                          dims=idx.shape, chunk=sp["chunk"],
                          unroll=sp["unroll"], compute=compute),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, Hkv, G, M, D), q.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, G, 1, M), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="bsa_selection_fwd",
        interpret=interpret,
    )(idx.reshape(-1), q, kb, vb, tok_bias[..., None])


def _resident_bwd_call(q, kb, vb, idx, tok_bias, do, lse, delta, *,
                       interpret, compute, buffers):
    B, Hkv, G, M, D = q.shape
    sp = _resident_specs(q, kb, buffers)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=sp["grid"],
        in_specs=[sp["row"], sp["slab"], sp["slab"], sp["bias"], sp["row"],
                  sp["stat"], sp["stat"]],
        out_specs=(sp["row"], sp["slab"], sp["slab"]))
    dq, dkb, dvb = pl.pallas_call(
        functools.partial(_resident_bwd_kernel, scale=1.0 / (D ** 0.5),
                          dims=idx.shape, chunk=sp["chunk"],
                          unroll=sp["unroll"], compute=compute),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, Hkv, G, M, D), q.dtype),
                   jax.ShapeDtypeStruct(kb.shape, jnp.float32),
                   jax.ShapeDtypeStruct(vb.shape, jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="bsa_selection_bwd",
        interpret=interpret,
    )(idx.reshape(-1), q, kb, vb, tok_bias[..., None], do, lse,
      delta[:, :, :, None])
    return dq, dkb.astype(kb.dtype), dvb.astype(vb.dtype)


# VMEM the resident schedule may fill (v5e has 128 MiB per core), and the
# limit handed to the compiler, which leaves it room for its own scratch.
_RESIDENT_VMEM_BYTES = 96 << 20
_VMEM_LIMIT_BYTES = 112 << 20
# Query groups per resident grid step, and groups per unrolled iteration.
_GROUPS_PER_STEP = 128
_UNROLL = 8


def _chunking(G: int) -> tuple[int, int]:
    """(Gc, unroll): groups per resident grid step, ⌈G / n⌉ for the fewest
    n steps of at most ``_GROUPS_PER_STEP`` (480 groups → 4 steps of 120),
    and the largest divisor of Gc up to ``_UNROLL``."""
    chunk = pl.cdiv(G, pl.cdiv(G, _GROUPS_PER_STEP))
    return chunk, max(u for u in range(1, _UNROLL + 1) if chunk % u == 0)


def _vmem_bytes(shape, dtype) -> int:
    """VMEM bytes of a block: its last two dims padded to the dtype's
    (sublane, 128-lane) tile — an (8, 32) fp32 tile fills a whole (8, 128)."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, cols = shape
    sub = 8 * 4 // itemsize
    return (math.prod(lead) * pl.cdiv(rows, sub) * sub * pl.cdiv(cols, 128)
            * 128 * itemsize)


def _resident_buffers(direction: str, q_shape, kb_shape, k_star: int,
                      dtype) -> int:
    """Slab buffers with which the resident ``direction`` ("fwd" / "bwd")
    fits ``_RESIDENT_VMEM_BYTES``: 2 (double-buffered, the next (b, h)'s
    slab loads behind this one's compute), else 1, else 0 (blocked).

    q (B, Hkv, G, M, D), kb (B, Hkv, NB, ℓ, D), both of ``dtype``.  Counted:
    the K, V and bias slabs (and in the backward the fp32 dK, dV slabs),
    the chunk's double-buffered row blocks, and ten (k*·ℓ, max(M, D)) fp32
    temporaries per unrolled group."""
    _, _, G, M, D = q_shape
    nb, ell = kb_shape[2], kb_shape[3]
    chunk, unroll = _chunking(G)
    f32 = jnp.float32
    slabs = 2 * _vmem_bytes((nb, ell, D), dtype) + _vmem_bytes((nb, ell, 1), f32)
    row, stat = _vmem_bytes((chunk, M, D), dtype), _vmem_bytes((chunk, 1, M), f32)
    if direction == "fwd":
        rows = 2 * (2 * row + stat)
    else:
        slabs += 2 * _vmem_bytes((nb, ell, D), f32)
        rows = 2 * (3 * row + 2 * stat)
    temps = unroll * 10 * _vmem_bytes((k_star * ell, max(M, D)), f32)
    for buffers in (2, 1):
        if buffers * slabs + rows + temps <= _RESIDENT_VMEM_BYTES:
            return buffers
    return 0


def selection_schedule(direction: str, q_shape, kb_shape, k_star: int,
                       dtype) -> str:
    """The schedule (and scope name) a launch of these kernel-level shapes
    takes in ``direction``: "resident" when its slab fits VMEM, else
    "blocked".  See ``_resident_buffers``."""
    fits = _resident_buffers(direction, q_shape, kb_shape, k_star, dtype)
    return "resident" if fits else "blocked"


@functools.lru_cache(maxsize=None)
def _make_vjp(interpret: bool, compute: str, fwd_buffers: int,
              bwd_buffers: int):
    """The differentiable call; ``*_buffers`` 0 runs that direction on the
    blocked grid, else resident with that many slab buffers."""
    kw = dict(interpret=interpret, compute=compute)

    def forward(q, kb, vb, idx, tok_bias):
        if fwd_buffers:
            with jax.named_scope("resident"):
                return _resident_fwd_call(q, kb, vb, idx, tok_bias,
                                          buffers=fwd_buffers, **kw)
        with jax.named_scope("blocked"):
            return _fwd_call(q, kb, vb, idx, tok_bias, **kw)

    @jax.custom_vjp
    def attend(q, kb, vb, idx, tok_bias):
        return forward(q, kb, vb, idx, tok_bias)[0]

    def attend_fwd(q, kb, vb, idx, tok_bias):
        o, lse = forward(q, kb, vb, idx, tok_bias)
        return o, (q, kb, vb, idx, tok_bias, o, lse)

    def attend_bwd(res, do):
        q, kb, vb, idx, tok_bias, o, lse = res
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        if bwd_buffers:
            with jax.named_scope("resident"):
                dq, dkb, dvb = _resident_bwd_call(
                    q, kb, vb, idx, tok_bias, do, lse, delta,
                    buffers=bwd_buffers, **kw)
        else:
            with jax.named_scope("blocked"):
                dq, dkb_sel, dvb_sel = _bwd_call(q, kb, vb, idx, tok_bias, do,
                                                 lse, delta, **kw)
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
    B, Hkv, G, k_star = idx.shape
    f = _make_vjp(interpret, compute,
                  *(_resident_buffers(d, q.shape, kb.shape, k_star, q.dtype)
                    for d in ("fwd", "bwd")))
    if interpret and q.shape[0] > 1:
        # CPU fallback: per-sample grids keep the interpreter linear in B
        f = functools.partial(interpret_batch_map, f)
    # the ids of one launch must fit SMEM: split the (B, Hkv) grid axes into
    # independent launches (every grid cell reads only its own (b, h) slice)
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
