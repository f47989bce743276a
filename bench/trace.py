"""From a profiler trace to the numbers the per-layer metrics read.

``events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``: the operations each accelerator ran (the
"XLA Ops" line of every ``/device:`` plane) and the benchmark's own host
spans (``TraceAnnotation`` names starting ``bench.``), on one clock.
``reduce`` turns them into busy time, idle gaps, time per kernel and the
device time inside each request.  The two are apart so that a recorded,
trimmed event list (``bench/tests/``) checks the reduction.
"""

from __future__ import annotations

import bisect
import glob
import re
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
REQUEST_SPAN = "bench.request"
OPS_LINE = "XLA Ops"
KERNEL_PREFIX = "bsa_"
# control flow whose event spans the operations it runs
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"\.\d+$")


def xplane_file(trace_dir: str | Path) -> Path:
    found = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return Path(found[0])


def events(path: str | Path) -> dict:
    """{"device": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[span name, start_ns, dur_ns], ...]}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            device[plane.name] = [[e.name, float(e.start_ns), float(e.duration_ns)]
                                  for e in lines[OPS_LINE].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    if not device:
        raise RuntimeError(f"no device plane with an {OPS_LINE!r} line in "
                           f"{path}: {[p.name for p in data.planes]}")
    return {"device": device, "host": host}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(merged, starts, s, e):
    """Time of the disjoint sorted intervals ``merged`` inside [s, e)."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < e:
        total += max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return total


class _Spans:
    """The benchmark's host spans, for finding the innermost one around a
    time and the span edges inside an interval."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda t: t[1])
        self.starts = [s for _, s, _ in self.spans]
        self.edges = sorted(t for _, s, e in spans for t in (s, e))

    def cuts(self, a, b):
        lo = bisect.bisect_right(self.edges, a)
        hi = bisect.bisect_left(self.edges, b)
        return [a] + self.edges[lo:hi] + [b]

    def label(self, t, depth=8):
        i = bisect.bisect_right(self.starts, t) - 1
        for j in range(i, max(i - depth, -1), -1):
            name, s, e = self.spans[j]
            if s <= t < e:
                return name
        return "outside benchmark spans"


def op_name(event_name: str) -> str:
    """An operation's HLO instruction name; a kernel's without its suffix.
    The TPU trace names an op by its HLO text:
    ``%bsa_ball_fwd.3 = (f32[...]) custom-call(...)`` -> ``bsa_ball_fwd``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", name) if name.startswith(KERNEL_PREFIX) else name


def reduce(ev: dict, top: int = 10) -> dict:
    """Busy time, idle gaps and per-kernel device time inside the benchmark's
    ``bench.window`` span, averaged over the devices that ran anything.

    Returns seconds throughout: ``window_s``, ``busy_s``; ``kernels``
    {name: {"seconds", "count"}} for the ``bsa_*`` kernels; ``device_ops``
    and ``idle_gaps``, the ``top`` largest as [name, seconds], a gap cut
    where benchmark spans begin and end, each piece named by the innermost
    span around it; ``requests``: per
    ``bench.request`` span, [span seconds, device-busy seconds inside]."""
    windows = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, got {len(windows)}")
    w0, w1 = windows[0]
    spans = [(n, s, s + d) for n, s, d in ev["host"] if n != WINDOW_SPAN]
    lookup = _Spans(spans)
    busy, ops, kernels, gaps = [], {}, {}, {}
    merged_all = []
    for plane in ev["device"].values():
        ivs = []
        for name, s, d in plane:
            a, b = max(s, w0), min(s + d, w1)
            key = op_name(name)
            if b <= a or _SUFFIX.sub("", key) in CONTAINERS:
                continue
            ivs.append((a, b))
            ops[key] = ops.get(key, 0.0) + (b - a)
            if key.startswith(KERNEL_PREFIX):
                k = kernels.setdefault(key, {"seconds": 0.0, "count": 0})
                k["seconds"] += (b - a) * 1e-9
                k["count"] += 1
        if not ivs:
            continue
        merged = _union(ivs)
        merged_all.append(merged)
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            # cut the gap where a span starts or ends; each piece goes to
            # the innermost span around it
            cuts = lookup.cuts(a, b)
            for x, y in zip(cuts, cuts[1:]):
                if y > x:
                    gaps.setdefault(lookup.label((x + y) / 2), []).append(y - x)
    if not busy:
        raise ValueError("no device operation ran inside the window")
    n_dev = len(busy)
    starts = [[iv[0] for iv in m] for m in merged_all]
    requests = [[(e - s) * 1e-9,
                 sum(_overlap(m, st, s, e) for m, st in zip(merged_all, starts))
                 * 1e-9 / n_dev]
                for n, s, e in spans if n == REQUEST_SPAN]
    idle = sorted(((label, g * 1e-9 / n_dev) for label, gs in gaps.items()
                   for g in gs), key=lambda t: -t[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) * 1e-9 / n_dev,
        "devices": n_dev,
        "kernels": kernels,
        "device_ops": sorted(([k, v * 1e-9 / n_dev] for k, v in ops.items()),
                             key=lambda t: -t[1])[:top],
        "idle_gaps": [list(g) for g in idle[:top]],
        "requests": requests,
    }
