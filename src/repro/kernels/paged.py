"""Paged-KV-cache row gather: scalar-prefetched pool-row DMA copies.

``paged_gather_kernel_call(pool (R, H, D), rows (M,) int32) → (M, H, D)``
pulls M arbitrary pool rows (block-table-resolved token or φ-block rows,
``core.nsa_causal.nsa_causal_decode_paged``).  One grid cell per row: the
row index is SCALAR-PREFETCHED, so each cell's input ``index_map`` points
its DMA straight at the pool row and Mosaic pipelines the copies across the
grid — the same ``PrefetchScalarGridSpec`` idiom the varlen kernels use for
per-tile segment ranges.  The kernel body is pure data movement; its point
is that the decode hot path's gathers stream through VMEM as overlapped
row DMAs instead of one monolithic XLA gather materialisation.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_gather_kernel_call"]


def _copy_kernel(rows_ref, pool_ref, out_ref):
    del rows_ref                       # consumed by the index_map
    out_ref[...] = pool_ref[...]


def paged_gather_kernel_call(pool, rows, *, interpret: bool):
    M = rows.shape[0]
    R, H, D = pool.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[pl.BlockSpec((1, H, D), lambda i, rr: (rr[i], 0, 0))],
        out_specs=pl.BlockSpec((1, H, D), lambda i, rr: (i, 0, 0)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, H, D), pool.dtype),
        name="bsa_paged_gather",
        interpret=interpret,
    )(rows, pool)
