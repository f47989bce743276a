"""Fused gated-combine epilogue Pallas kernel.

``bsa_attention`` / ``nsa_causal_attention`` end by sigmoid-gating their
three branch outputs and masking padded queries.  Composed in jnp that is
three fp32 upcast temporaries + three multiplies + two adds + a select —
seven HBM round-trips over (B, N, H, D) data.  This kernel does the whole
epilogue in ONE pass:

    out = (g_ball·o_ball + g_cmp·o_cmp + g_slc·o_slc) · m

Layout: branch outputs are flattened to rows (R, D) with R = B·N·H; gates
and the query-validity mask become per-row (R, 1) fp32 columns (the
broadcast over D happens in-register).  Purely elementwise → VPU work, grid
over row tiles.  The row tile is chosen by the wrapper (``kernels/ops.py``),
which pads R up to a tile multiple and slices the pad off after.

Differentiable in the branch outputs AND the gates (gates are parameters):
    d_o_b = g_b · m · do              d_g_b = m · Σ_D(do · o_b)
computed by a second elementwise kernel on the same grid.  The mask is a
mask — its cotangent is dropped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import should_interpret

__all__ = ["gated_combine_kernel_call"]


def _fwd_kernel(o1_ref, o2_ref, o3_ref, g1_ref, g2_ref, g3_ref, m_ref, out_ref):
    acc = (g1_ref[...] * o1_ref[...].astype(jnp.float32)
           + g2_ref[...] * o2_ref[...].astype(jnp.float32)
           + g3_ref[...] * o3_ref[...].astype(jnp.float32))
    out_ref[...] = (acc * m_ref[...]).astype(out_ref.dtype)


def _bwd_kernel(o1_ref, o2_ref, o3_ref, g1_ref, g2_ref, g3_ref, m_ref, do_ref,
                do1_ref, do2_ref, do3_ref, dg1_ref, dg2_ref, dg3_ref):
    do = do_ref[...].astype(jnp.float32) * m_ref[...]      # (t, D) masked cotangent
    for o_ref, g_ref, dout_ref, dg_ref in (
            (o1_ref, g1_ref, do1_ref, dg1_ref),
            (o2_ref, g2_ref, do2_ref, dg2_ref),
            (o3_ref, g3_ref, do3_ref, dg3_ref)):
        dout_ref[...] = (g_ref[...] * do).astype(dout_ref.dtype)
        dg_ref[...] = jnp.sum(do * o_ref[...].astype(jnp.float32),
                              axis=-1, keepdims=True)


def _specs(t: int, D: int):
    row = pl.BlockSpec((t, D), lambda i: (i, 0))
    col = pl.BlockSpec((t, 1), lambda i: (i, 0))
    return row, col


def _fwd_call(o1, o2, o3, g1, g2, g3, m, *, tile, interpret):
    R, D = o1.shape
    row, col = _specs(tile, D)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(R // tile,),
        in_specs=[row, row, row, col, col, col, col],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((R, D), o1.dtype),
        name="bsa_epilogue_fwd",
        interpret=interpret,
    )(o1, o2, o3, g1, g2, g3, m)


def _bwd_call(o1, o2, o3, g1, g2, g3, m, do, *, tile, interpret):
    R, D = o1.shape
    row, col = _specs(tile, D)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(R // tile,),
        in_specs=[row, row, row, col, col, col, col, row],
        out_specs=(row, row, row, col, col, col),
        out_shape=(jax.ShapeDtypeStruct((R, D), o1.dtype),
                   jax.ShapeDtypeStruct((R, D), o2.dtype),
                   jax.ShapeDtypeStruct((R, D), o3.dtype),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)),
        name="bsa_epilogue_bwd",
        interpret=interpret,
    )(o1, o2, o3, g1, g2, g3, m, do)


@functools.lru_cache(maxsize=None)
def _make_vjp(tile: int, interpret: bool):
    kw = dict(tile=tile, interpret=interpret)

    @jax.custom_vjp
    def combine(o1, o2, o3, g1, g2, g3, m):
        return _fwd_call(o1, o2, o3, g1, g2, g3, m, **kw)

    def combine_fwd(o1, o2, o3, g1, g2, g3, m):
        out = _fwd_call(o1, o2, o3, g1, g2, g3, m, **kw)
        return out, (o1, o2, o3, g1, g2, g3, m)

    def combine_bwd(res, do):
        o1, o2, o3, g1, g2, g3, m = res
        do1, do2, do3, dg1, dg2, dg3 = _bwd_call(o1, o2, o3, g1, g2, g3, m, do,
                                                 **kw)
        return do1, do2, do3, dg1, dg2, dg3, None          # mask: no grad

    combine.defvjp(combine_fwd, combine_bwd)
    return combine


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def gated_combine_kernel_call(o1, o2, o3, g1, g2, g3, m, *, tile: int,
                              interpret: bool | None = None):
    """Row-flattened fused epilogue.

    o1..o3: (R, D) branch outputs (any floating dtype);
    g1..g3: (R, 1) fp32 per-row gate values;
    m:      (R, 1) fp32 query-validity (1.0 real / 0.0 padded);
    ``tile`` must divide R (the wrapper pads R up to a multiple).
    Returns (R, D) in o1's dtype.  Differentiable in o1..o3 and g1..g3.
    """
    assert o1.shape[0] % tile == 0, \
        f"rows {o1.shape[0]} not a multiple of tile {tile} (wrapper must pad)"
    if interpret is None:
        interpret = should_interpret()
    return _make_vjp(tile, interpret)(o1, o2, o3, g1, g2, g3, m)
