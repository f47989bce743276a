"""Tile autotuner for the Pallas kernels (+ the shared tile heuristic).

Tile-shape choice dominates sparse-kernel throughput (Block Sparse Flash
Attention's headline result), so instead of a fixed divisor rule the flash
kernel's ``(tq, tk)`` tiles come from a three-stage policy:

  1. **Cache hit** — a JSON cache persisted at ``$REPRO_TUNING_CACHE`` (a
     committed file, or one the caller owns; unset = in-memory only, so
     nothing outside the checkout decides a tile) keyed by
     ``(kernel, shape-bucket, head_dim, dtype, interpret|compiled)``.  Shape
     buckets are next-power-of-two, so one measurement covers a band of
     ragged lengths.  A hit never re-measures — the second run of any shape
     is pure lookup.
  2. **Measured** — when autotuning is enabled (``$REPRO_AUTOTUNE=1`` or the
     ``--autotune`` flag of ``benchmarks/perf_iter.py``), the candidate grid
     is swept with timed compiled runs of the real kernel at the bucketed
     shape and the winner is persisted.  Measurement happens at trace time
     on concrete throwaway inputs (the Triton-autotune pattern), so jitted
     callers pay it once per bucket, ever.
  3. **Heuristic fallback** — otherwise :func:`heuristic_tile`, a
     deterministic rule that never degenerates: tiles are lane-aligned in
     ``[pref // 2, pref]`` and callers PAD the axis up to a tile multiple
     (see ``kernels/ops.py``) instead of shrinking the tile to a tiny
     divisor.  Interpret mode (CI) always lands here unless a cache entry
     already exists, so CI stays fast and deterministic.

The kernel wrappers own the padding contract that makes non-divisor tiles
legal: padded KEYS are masked with ``NEG_INF`` bias (zero contribution and
exactly zero gradient), padded QUERY rows are computed and sliced off.
"""

from __future__ import annotations

import json
import os
import tempfile
import time
from pathlib import Path

__all__ = [
    "ENV_CACHE",
    "ENV_AUTOTUNE",
    "autotune_enabled",
    "cache_path",
    "clear_memory_cache",
    "clamp_tile",
    "heuristic_tile",
    "round_up",
    "shape_bucket",
    "flash_candidates",
    "flash_variant",
    "get_tiles",
    "tune_measure_flash",
    "tune_flash",
]

ENV_CACHE = "REPRO_TUNING_CACHE"
ENV_AUTOTUNE = "REPRO_AUTOTUNE"

# In-memory mirror of the JSON file: {path: {key: record}}.  Keyed by path so
# tests pointing $REPRO_TUNING_CACHE at a tmpdir never see stale state.
_MEM: dict[str, dict] = {}


def autotune_enabled() -> bool:
    return os.environ.get(ENV_AUTOTUNE, "") not in ("", "0", "false", "False")


def cache_path() -> Path | None:
    """The persisted cache file, or None (unset: in-memory only)."""
    env = os.environ.get(ENV_CACHE)
    return Path(env).expanduser() if env else None


def clear_memory_cache() -> None:
    """Drop the in-memory mirror (tests; the JSON file is untouched)."""
    _MEM.clear()


def _load() -> dict:
    p = cache_path()
    key = str(p)
    if key not in _MEM:
        try:
            _MEM[key] = json.loads(p.read_text()) if p else {}
        except (OSError, ValueError):
            _MEM[key] = {}
    return _MEM[key]


def _save(cache: dict) -> None:
    p = cache_path()
    _MEM[str(p)] = cache
    if p is None:
        return
    try:
        p.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=str(p.parent), suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, p)                    # atomic: concurrent runs race safely
    except OSError:
        pass                                  # read-only FS: in-memory cache still works


# ---------------------------------------------------------------------------
# Deterministic heuristic (the no-measurement path)
# ---------------------------------------------------------------------------

def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def shape_bucket(n: int) -> int:
    """Next power of two ≥ n — one cache entry per band of ragged lengths."""
    return 1 << max(n - 1, 1).bit_length() if n > 1 else 1


LANE = 128                                    # TPU vreg lane width


def heuristic_tile(n: int, pref: int) -> int:
    """Tile for an axis of length ``n`` with preference ``pref`` (a multiple
    of ``LANE``).

    Never degenerates, and always lowers for Mosaic: an axis that fits in
    one tile gets the whole axis rounded up to the 8-row sublane; a longer
    axis gets a ``LANE`` multiple in ``[pref // 2, pref]`` — a block's last
    dim must be a multiple of 128 or the whole axis, and the per-key bias
    and per-row lse blocks carry the tile on that dim.  When the tile does
    not divide ``n`` the CALLER pads the axis up to a multiple
    (``kernels/ops.py``) — the old rule of shrinking to the largest divisor
    collapsed to tile size 1 on prime-ish lengths (e.g. ragged
    ``bucket_length`` leftovers), serialising the grid.
    """
    if n <= pref:
        return round_up(n, 8)                 # single tile, ≤ 7 padded rows
    best = pref
    for t in range(pref, pref // 2 - 1, -LANE):   # lane-aligned divisor search
        if n % t == 0:
            return t
        if round_up(n, t) - n < round_up(n, best) - n:
            best = t                          # least padding among candidates
    return best


def clamp_tile(t: int, n: int, *, interpret: bool) -> int:
    """Fit a requested tile to an axis of length ``n``: at most the whole
    axis (rounded up to the sublane), and — for compiled kernels — a
    ``LANE`` multiple, the only other block width Mosaic accepts.  The
    interpreter has no layout rule, so explicit small tiles stay as given
    there (tests use them to exercise many-tile grids on tiny shapes)."""
    if not interpret:
        t = round_up(t, LANE)
    return min(t, round_up(n, 8))


# ---------------------------------------------------------------------------
# The cache + measurement policy
# ---------------------------------------------------------------------------

def _key(kernel: str, *, n_q: int, n_k: int, d: int, dtype, interpret: bool,
         variant: str = "", layout: str = "", compute: str = "") -> str:
    mode = "interpret" if interpret else "compiled"
    v = f"/{variant}" if variant else ""
    lay = f"/{layout}" if layout else ""
    # compute = the matmul-OPERAND dtype of the precision contract.  It joins
    # the key only when it differs from the storage dtype's own resolution,
    # so pre-contract cache entries stay valid for the default path.
    cmp_ = f"/c:{compute}" if compute and compute != "float32" else ""
    return (f"{kernel}/q{shape_bucket(n_q)}_k{shape_bucket(n_k)}_d{d}"
            f"/{str(dtype)}/{mode}{v}{lay}{cmp_}")


def flash_variant(causal: bool, block_causal: bool, ell: int) -> str:
    """Cache-key component for the flash mask mode — different in-kernel
    masking does different work, so tiles are tuned per mode."""
    if causal:
        return "causal"
    if block_causal:
        return f"blockcausal{ell}"
    return "plain"


def flash_candidates(n_q: int, n_k: int) -> list[tuple[int, int]]:
    """Candidate (tq, tk) grid (tiles ≤ the pow2 shape buckets, which they
    therefore divide exactly — measurement happens at the bucketed shape)."""
    bq, bk = shape_bucket(n_q), shape_bucket(n_k)
    cands = [(tq, tk)
             for tq in (64, 128, 256, 512) if tq <= bq
             for tk in (128, 256, 512) if tk <= bk]
    return cands or [(heuristic_tile(n_q, 256), heuristic_tile(n_k, 256))]


def get_tiles(kernel: str, *, n_q: int, n_k: int, d: int, dtype,
              interpret: bool, measure=None, variant: str = "",
              layout: str = "", compute: str = "",
              prefs: tuple[int, int] = (256, 256)) -> tuple[int, int]:
    """Resolve (tq, tk) for one kernel launch.

    ``variant`` distinguishes configurations of one kernel whose in-kernel
    work differs (flash mask modes) so they never share a cache entry.
    ``layout`` distinguishes the batch layout — "" for padded-bucket
    (B, L) batches vs ``"varlen"`` for the packed-offsets layout, whose
    per-tile segment masking / tile skipping changes the cost profile, so a
    tile measured on one layout must never be replayed on the other.
    ``compute`` is the matmul-operand dtype of the precision contract
    (``common.resolve_compute_dtype``) — a tile tuned under bf16 or fp8
    operands is never replayed for fp32 compute, and vice versa.
    ``measure(tq, tk) -> seconds`` is invoked per candidate ONLY on a cache
    miss with autotuning enabled; the winner is persisted.  Without a measure
    callback (or with autotune off / measure failure) the deterministic
    heuristic is returned and nothing is written.
    """
    key = _key(kernel, n_q=n_q, n_k=n_k, d=d, dtype=dtype, interpret=interpret,
               variant=variant, layout=layout, compute=compute)
    cache = _load()
    hit = cache.get(key)
    if hit:
        return tuple(hit["tiles"])
    fallback = (heuristic_tile(n_q, prefs[0]), heuristic_tile(n_k, prefs[1]))
    if measure is None or not autotune_enabled():
        return fallback
    timings = {}
    for tq, tk in flash_candidates(n_q, n_k):
        try:
            timings[(tq, tk)] = float(measure(tq, tk))
        except Exception:                     # candidate OOM/unsupported: skip
            continue
    if not timings:
        return fallback
    best = min(timings, key=timings.get)
    cache[key] = {"tiles": list(best), "us": round(timings[best] * 1e6, 1),
                  "candidates": {f"{a}x{b}": round(t * 1e6, 1)
                                 for (a, b), t in sorted(timings.items())},
                  "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S")}
    _save(cache)
    return best


def tune_measure_flash(tq: int, tk: int, *, n_q: int, n_k: int, d: int, dtype,
                       interpret: bool, causal: bool = False,
                       block_causal: bool = False, ell: int = 1,
                       bh: int = 2, iters: int = 3) -> float:
    """Time one (tq, tk) candidate of the flash kernel, in seconds.

    Builds throwaway inputs at the BUCKETED shape (so the measurement is
    valid for the whole cache band) and times the real
    ``flash_attention_kernel_call`` — median of ``iters`` after one
    compile/warmup call.  Runs eagerly on concrete data, so it is safe to
    invoke from a traced caller (the Triton-autotune pattern).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash import flash_attention_kernel_call

    bq, bk = shape_bucket(n_q), shape_bucket(n_k)
    nq, nk = round_up(bq, tq), round_up(bk, tk)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (bh, 1, nq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (bh, nk, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (bh, nk, d), jnp.float32).astype(dtype)
    bias = jnp.zeros((1, nk), jnp.float32)

    def run():
        return flash_attention_kernel_call(
            q, k, v, bias, n_heads=bh, causal=causal,
            block_causal=block_causal, ell=ell, tq=tq, tk=tk,
            interpret=interpret)

    jax.block_until_ready(run())              # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def tune_flash(*, n_q: int, n_k: int, d: int, dtype, interpret: bool,
               bh: int = 2, causal: bool = False, block_causal: bool = False,
               ell: int = 1, iters: int = 3) -> tuple[int, int]:
    """Measure + persist the flash kernel's tiles for one shape bucket.

    Honours the cache: a hit returns immediately without measuring, which is
    what makes a second ``--autotune`` run measurement-free.
    """
    def measure(tq, tk):
        return tune_measure_flash(tq, tk, n_q=n_q, n_k=n_k, d=d, dtype=dtype,
                                  interpret=interpret, causal=causal,
                                  block_causal=block_causal, ell=ell, bh=bh,
                                  iters=iters)

    return get_tiles("flash", n_q=n_q, n_k=n_k, d=d, dtype=dtype,
                     interpret=interpret, measure=measure,
                     variant=flash_variant(causal, block_causal, ell))
