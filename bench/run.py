#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` names the loop in
``bench/kinds/``).  The run sets up (inputs and weights from ``--seed``,
every shape compiled or loaded from the persistent compilation cache, the
first steps or requests), measures for ``--seconds``, then checks what the
timed path produced against the plain reference (``bench/compare.py``,
limits in ``bench/limits/<cell>.json``).  With ``--trace 1`` the window runs
under the profiler and the result carries the cell's per-layer metrics
(``bench/metrics/<metric>.py``) instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` [, ``breakdown``], and
last ``checks``: each number compared, with its limit.  Those numbers are
also the last lines of standard error.  Exits 2, printing no result, without
a TPU (or with fewer than the cell's chips), for a device kind that
``bench/peaks.json`` does not list, or when the environment would take the
run off the compiled kernels.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is timed from here

import argparse                        # noqa: E402
import gc                              # noqa: E402
import importlib                       # noqa: E402
import importlib.util                  # noqa: E402
import json                            # noqa: E402
import os                              # noqa: E402
import shutil                          # noqa: E402
import sys                             # noqa: E402
import tempfile                        # noqa: E402
from pathlib import Path               # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# one process with few threads: the host's linear algebra (set-up only) keeps
# no pool of spinning threads beside the window's host path
for _var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_var, "1")


def refuse(msg: str) -> None:
    print(f"[bench] refusing to run: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def off_kernel_overrides() -> list[str]:
    """Environment settings under which the run would not measure the
    compiled kernels with the tiles the code chooses."""
    env = os.environ
    bad = []
    if env.get("REPRO_PALLAS_INTERPRET", "0") not in ("0", "false", "False"):
        bad.append("REPRO_PALLAS_INTERPRET (forces the Pallas interpreter)")
    if env.get("REPRO_ATTENTION_BACKEND", "pallas") != "pallas":
        bad.append("REPRO_ATTENTION_BACKEND (overrides the pallas backend)")
    if env.get("REPRO_SHARDED_INNER", "pallas") != "pallas":
        bad.append("REPRO_SHARDED_INNER (overrides the sharded inner backend)")
    if env.get("REPRO_AUTOTUNE", "") not in ("", "0", "false", "False"):
        bad.append("REPRO_AUTOTUNE (tiles would be measured, not chosen)")
    if env.get("REPRO_TUNING_CACHE"):
        bad.append("REPRO_TUNING_CACHE (tiles would come from that file)")
    return bad


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """The cell's workload entry with its configuration, traffic mix and
    metrics, each found by the name ``BENCHMARK.json`` gives it."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    mine = lambda ms: [m for m in ms if name in m.get("workloads", [name])]
    return {"name": name, "chips": w["chips"], "config": config,
            "traffic": traffic, "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             peak: dict | None, device, t_start: float) -> dict:
    """Set up, measure, check.  Returns the result object."""
    import jax

    from bench import compare
    from bench import trace as tr

    run = importlib.import_module(f"bench.kinds.{cell['traffic']['kind']}"
                                  ).Run(cell, seed)
    setup_s = time.perf_counter() - t_start
    # set-up's garbage is collected before the window, and what set-up keeps
    # is not scanned again inside it
    gc.collect()
    gc.freeze()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        # host spans and device ops; no Python call tracing, which would
        # slow the host path the window measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        record = run.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    reduced = None
    if trace:
        try:
            reduced = tr.reduce(tr.events(tr.xplane_file(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # the allocator counts the compiled programs' temporaries as reserved
    # bytes, apart from the arrays in use
    stats = device.memory_stats() or {}
    memory_peak = (int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    numbers = run.check()
    correct, checks = compare.judge(numbers, compare.load_limits(cell["name"]))

    metrics = {}
    if trace:
        rec = {"cell": cell, "window": record, "trace": reduced, "peak": peak,
               "work": run.work(record["attempted"])}
        for m in cell["per_layer"]:
            value = _module(BENCH / "metrics" / f"{m['name']}.py").read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else record[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": dev}
    if trace:
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    print(f"[bench] {cell['name']} seed {seed}: window {record['seconds']:.3f} s, "
          f"{record['attempted']} done, setup {setup_s:.2f} s", file=sys.stderr)
    for k in sorted(set(numbers) - set(checks)):
        print(f"[bench] not compared {k}: {numbers[k]:.6g}", file=sys.stderr)
    for k, c in checks.items():
        print(f"[bench] check {k}: {c['value']:.6g} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    out["checks"] = checks
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bad = off_kernel_overrides()
    if bad:
        refuse("set in the environment: " + "; ".join(bad))
    if not (ROOT / "src" / "repro").is_dir():
        refuse(f"the program (src/repro) is not in {ROOT}")
    # the checkout and the program, in place of this script's directory
    # (whose module names would shadow the standard library's)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    cell = load_cell(args.workload)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        refuse(f"no TPU: JAX sees {len(devices)} {dev.platform} device(s)")
    if len(devices) < cell["chips"]:
        refuse(f"{args.workload} needs {cell['chips']} chips, found {len(devices)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if dev.device_kind not in peaks:
        refuse(f"device kind {dev.device_kind!r} is not in bench/peaks.json")

    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   peaks[dev.device_kind], dev, T_START)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
