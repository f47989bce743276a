"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state.  Target: TPU v5e pods — 16×16 = 256 chips per pod; multi-pod adds a
leading ``pod`` axis (2 pods = 512 chips) whose collectives cross DCI.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    have = len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"make_production_mesh targets a {'2-pod ' if multi_pod else ''}"
            f"16x16 v5e pod ({need} devices) but only {have} device(s) are "
            "present; use make_local_mesh() (or make_mesh() with an explicit "
            "shape) for smaller hosts")
    return make_mesh(shape, axes)


def make_local_mesh(n_data: int | None = None, *, axis: str = "data"):
    """1-D mesh over however many devices actually exist.

    The mesh the CPU smoke runs and the ``"sharded"`` attention backend use
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` fakes devices
    for CI).  ``n_data`` takes the first n devices; default is all of them.
    """
    have = len(jax.devices())
    n = n_data if n_data is not None else have
    if n < 1 or n > have:
        raise RuntimeError(
            f"make_local_mesh(n_data={n}): {have} device(s) present")
    return jax.make_mesh((n,), (axis,), (AxisType.Auto,),
                         devices=jax.devices()[:n])


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    # Auto axes: shardings come from the specs this repo gives (jax's own
    # default is Explicit, which would type every array by its sharding)
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


# v5e hardware constants (per chip) — used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW_PER_LINK = 50e9          # B/s per link


def ring_roofline_us(bytes_per_hop: int, hops: int,
                     links: int = 1) -> float:
    """ICI time (µs) of a ring-attention schedule on the roofline model.

    Each hop pushes one K/V slab to the ring neighbour over ``links`` ICI
    links; hops overlap with compute in steady state, so this is the lower
    bound the per-hop compute must exceed for the rotation to be free
    (``benchmarks/perf_iter.py --ring`` stamps it next to the measured
    ratios)."""
    return hops * bytes_per_hop / (links * ICI_BW_PER_LINK) * 1e6
