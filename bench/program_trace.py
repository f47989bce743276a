#!/usr/bin/env python3
"""The program's own host spans and device scopes in a profiler trace.

``bench/trace.py`` reads the device ops and the benchmark's ``bench.*``
spans.  This module reads, from the same ``.xplane.pb``, also:

* the program's host spans: ``TraceAnnotation`` names under ``repro.``
  (``GeometryEngine.predict``, ``Trainer.fit``), with their arguments;
* each device op's scope path: the ``jax.named_scope`` names (``SCOPES``)
  in the op's HLO ``op_name``, e.g. ``jit(_forward)/while/body/bsa/
  selection/topk/top_k`` -> ``bsa/selection/topk``.  On the v5e the
  ``.xplane.pb``'s op events carry no ``op_name``; the trace-viewer export
  the profiler writes beside it (``*.trace.json.gz``) does, as each op
  event's ``tf_op`` argument, resolved from the HLO in the trace's metadata
  plane.  The two are joined on the plane, the line and the op's
  ``device_offset_ps``;
* JAX's own compile spans (``backend_compile*``).

``reduce`` returns what ``bench.trace.reduce`` returns for the same events,
with the idle gaps cut and labelled by the innermost span of either family,
and adds ``spans``, ``scopes`` and ``compiles``.  On a trace with no
``repro.*`` span and no scope, the result is ``bench.trace.reduce``'s
exactly.  ``selection_share`` and ``engine_prep_ms`` read it as the
benchmark's per-layer readers read ``bench.trace.reduce``.

    python bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

sets a cell up as ``bench/run.py`` does, traces its window, and prints the
reduction with the two readings as one JSON line; ``--events FILE`` also
writes the event list (gzipped JSON, the form ``reduce`` takes).
"""

from __future__ import annotations

import bisect
import gzip
import json
import re
import sys
from pathlib import Path

if __package__ in (None, ""):
    # run as a script: the checkout and the program, in place of this
    # script's directory (whose module names would shadow the standard
    # library's)
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "repro."
COMPILE_PREFIX = "backend_compile"
# the program's jax.named_scope names (docs/architecture.md, Tracing)
SCOPES = frozenset({"bsa", "ball", "compression", "selection", "score", "topk",
                    "attend", "combine", "attention", "embed", "norm",
                    "attn_proj", "ffn", "head", "loss", "clip", "optimizer"})
SELECTION = "bsa/selection"
ENGINE_PREP = ("repro.engine.balltree", "repro.engine.pack",
               "repro.engine.unpack")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def scope_path(op_name: str) -> str:
    """The program's scopes in an HLO ``op_name``, outermost first.  A
    transformation wraps a scope (``transpose(jvp(loss))`` is ``loss``); a
    jitted function's name (``jit(norm)``) is no scope, nor is the last
    component, the primitive.  A fusion's ``tf_op`` lists the names of the
    ops it fused, ``;`` apart: the first is its own.  Where the profiler
    joins a called computation's names to its caller's, a scope met again
    is entered again (``bsa/.../bsa/compression`` is ``bsa/compression``)."""
    out = []
    for part in op_name.split(";", 1)[0].split("/")[:-1]:
        m = _WRAPPED.match(part)
        while m and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
            m = _WRAPPED.match(part)
        if not m and part in SCOPES:
            if part in out:
                del out[out.index(part):]
            out.append(part)
    return "/".join(out)


def op_names(xplane: str | Path) -> dict:
    """{(device plane, line, device_offset_ps): HLO op_name} of the ops in
    the trace-viewer export beside ``xplane``; empty without one."""
    found = sorted(Path(xplane).parent.glob("*.trace.json.gz"))
    if not found:
        return {}
    ev = json.loads(gzip.decompress(found[0].read_bytes()))["traceEvents"]
    meta = {(e["pid"], e.get("tid")): e["args"]["name"] for e in ev
            if e.get("ph") == "M" and e.get("name") in ("process_name", "thread_name")}
    return {(meta.get((e["pid"], None)), meta.get((e["pid"], e["tid"])),
             str(e["args"]["device_offset_ps"])): e["args"]["tf_op"]
            for e in ev if e.get("ph") == "X"
            and "tf_op" in e.get("args", {}) and "device_offset_ps" in e["args"]}


def events(path: str | Path) -> dict:
    """``bench.trace.events``'s lists, with ``[name, start_ns, dur_ns,
    scope path]`` for each device op and ``[name, start_ns, dur_ns, {arg:
    value}]`` for each ``repro.*`` or compile span beside the ``bench.*``
    ones."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    names = op_names(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if trace.OPS_LINE not in lines:
                continue
            key = lambda e: (plane.name, trace.OPS_LINE,
                             str(dict(e.stats).get("device_offset_ps")))
            device[plane.name] = [
                [e.name, float(e.start_ns), float(e.duration_ns),
                 scope_path(names.get(key(e), ""))]
                for e in lines[trace.OPS_LINE].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(trace.SPAN_PREFIX):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
                    elif e.name.startswith((PROGRAM_PREFIX, COMPILE_PREFIX)):
                        args = {k: v for k, v in e.stats if not k.startswith("_")}
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), args])
    if not device:
        raise RuntimeError(f"no device plane with an {trace.OPS_LINE!r} line "
                           f"in {path}: {[p.name for p in data.planes]}")
    return {"device": device, "host": host}


class _Innermost:
    """The innermost of nested spans around each of a rising sequence of
    times: the latest begun of those still open."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda t: (t[1], -t[2]))
        self.next, self.open = 0, []

    def __call__(self, t: float) -> str:
        while self.next < len(self.spans) and self.spans[self.next][1] <= t:
            self.open.append(self.spans[self.next])
            self.next += 1
        while self.open and self.open[-1][2] <= t:
            self.open.pop()
        return self.open[-1][0] if self.open else "outside benchmark spans"


def _self_seconds(spans) -> list[float]:
    """Each span's duration less the time its child spans cover, in s."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1], -spans[i][2]))
    covered, stack = [0.0] * len(spans), []
    for i in order:
        _, s, e = spans[i]
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= spans[stack[-1]][2]:
            covered[stack[-1]] += e - s
        stack.append(i)
    return [(e - s - c) * 1e-9 for (_, s, e), c in zip(spans, covered)]


def reduce(ev: dict, top: int = 10) -> dict:
    """``bench.trace.reduce`` of the same events, ``idle_gaps`` cut and
    named by the innermost ``bench.*`` or ``repro.*`` span, and, in seconds:

    ``spans``: per ``repro.*`` name begun in the window, ``count``,
    ``seconds`` and ``self_seconds``; ``scopes``: per scope path, the
    device-busy time of its ops (a union per device, averaged over devices
    as ``busy_s``; "" for ops under no scope); ``compiles``: ``count`` and
    ``seconds`` of the compile spans begun in the window."""
    out = trace.reduce({"host": [h[:3] for h in ev["host"]
                                 if h[0].startswith(trace.SPAN_PREFIX)],
                        "device": {p: [o[:3] for o in ops]
                                   for p, ops in ev["device"].items()}}, top)
    (w0, w1), = [(s, s + d) for n, s, d, *_ in ev["host"]
                 if n == trace.WINDOW_SPAN]
    labelled = [(n, s, s + d) for n, s, d, *_ in ev["host"]
                if n != trace.WINDOW_SPAN
                and n.startswith((trace.SPAN_PREFIX, PROGRAM_PREFIX))]
    edges = sorted(t for _, s, e in labelled for t in (s, e))
    gaps, scopes, n_dev = {}, {}, 0
    for plane in ev["device"].values():
        by_scope = {}
        for name, s, d, *scope in plane:
            a, b = max(s, w0), min(s + d, w1)
            if b > a and trace._SUFFIX.sub("", trace.op_name(name)) not in trace.CONTAINERS:
                by_scope.setdefault(scope[0] if scope else "", []).append((a, b))
        if not by_scope:
            continue
        n_dev += 1
        for path, ivs in by_scope.items():
            scopes[path] = scopes.get(path, 0.0) + sum(
                b - a for a, b in trace._union(ivs))
        merged = trace._union([iv for ivs in by_scope.values() for iv in ivs])
        label = _Innermost(labelled)
        for a, b in zip([w0] + [e for _, e in merged], [s for s, _ in merged] + [w1]):
            lo = bisect.bisect_right(edges, a)
            hi = bisect.bisect_left(edges, b)
            cuts = [a] + edges[lo:hi] + [b]
            for x, y in zip(cuts, cuts[1:]):
                if y > x:
                    gaps.setdefault(label((x + y) / 2), []).append(y - x)
    idle = sorted(((name, g * 1e-9 / n_dev) for name, gs in gaps.items()
                   for g in gs), key=lambda t: -t[1])
    out["idle_gaps"] = [list(g) for g in idle[:top]]

    program = [(n, s, s + d) for n, s, d, *_ in ev["host"]
               if n.startswith(PROGRAM_PREFIX) and w0 <= s < w1]
    spans = {}
    for (name, s, e), own in zip(program, _self_seconds(program)):
        t = spans.setdefault(name, {"count": 0, "seconds": 0.0, "self_seconds": 0.0})
        t["count"] += 1
        t["seconds"] += (e - s) * 1e-9
        t["self_seconds"] += own
    compiles = [d for n, s, d, *_ in ev["host"]
                if n.startswith(COMPILE_PREFIX) and w0 <= s < w1]
    out["spans"] = spans
    out["scopes"] = {k: v * 1e-9 / n_dev for k, v in sorted(scopes.items())}
    out["compiles"] = {"count": len(compiles), "seconds": sum(compiles) * 1e-9}
    return out


def selection_share(rec: dict) -> float | None:
    """% of the window in which the device ran the selection branch (scores,
    top-k and the selection kernel; forward, remat recompute and backward)."""
    t = rec["trace"]
    sel = [v for k, v in t.get("scopes", {}).items()
           if k == SELECTION or k.startswith(SELECTION + "/")]
    return 100.0 * sum(sel) / t["window_s"] if sel else None


def engine_prep_ms(rec: dict) -> float | None:
    """Per ``GeometryEngine.predict``, the self time of its batches' ball
    trees, packing and un-permuting, in ms: the host work the device waits
    for in a closed loop."""
    spans = rec["trace"].get("spans", {})
    if "repro.engine.predict" not in spans:
        return None
    prep = sum(spans[n]["self_seconds"] for n in ENGINE_PREP if n in spans)
    return 1e3 * prep / spans["repro.engine.predict"]["count"]


def main() -> None:
    import argparse
    import gzip
    import importlib
    import json
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--events", help="also write the event list here (.json.gz)")
    args = ap.parse_args()

    from bench import run

    bad = run.off_kernel_overrides()
    if bad:
        run.refuse("set in the environment: " + "; ".join(bad))
    cell = run.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        run.refuse(f"no TPU: JAX sees {jax.devices()[0].platform}")
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    r = importlib.import_module(f"bench.kinds.{cell['traffic']['kind']}"
                                ).Run(cell, args.seed)
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            record = r.window(args.seconds)
        finally:
            jax.profiler.stop_trace()
        ev = events(trace.xplane_file(d))
    if args.events:
        Path(args.events).write_bytes(gzip.compress(json.dumps(ev).encode()))
    red = reduce(ev)
    rec = {"trace": red}
    red.pop("requests")
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "attempted": record["attempted"],
                      "window_seconds": record["seconds"],
                      "selection_share": selection_share(rec),
                      "engine_prep_ms": engine_prep_ms(rec),
                      "reduced": red}), flush=True)


if __name__ == "__main__":
    main()
