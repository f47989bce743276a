#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process.

    python bench/control.py --workload <cell> --program-seeds 1 2 ... \
        --control-seeds 7 8 9 [--seconds 2] [--compare-in float32 ...]

For each program seed: a run as ``bench/run.py`` makes it, with a short
window, and the numbers its check compares (the lower readings).  For each
control seed, each reading compared as the program is (the upper readings):

* ``control_bf16_program``: the configuration's float32 activations one step
  lower, on the program's own path: the same run with the model's
  ``compute_dtype`` set to bfloat16 (activations, q/k/v and attention tiles
  in bfloat16, float32 accumulation and softmax statistics);
* ``control_bf16_reference``: the reference put in the program's place,
  computing in bfloat16 throughout (``pointcloud_ref.BFLOAT16``);
* ``fault_half_batch`` (training cells): the reference in the program's
  place, each step learning from half its batch.

``--compare-in`` also compares every reading with the reference in other
precisions (``_extra``), printed under ``numbers_<name>``.  Prints one JSON
line per reading.  Needs the accelerator the cell asks for, as run.py does.
"""

from __future__ import annotations

import argparse
import copy
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
PLANTS = ("control_bf16_program", "control_bf16_reference", "fault_half_batch")


def _extra():
    from bench.configs import pointcloud_ref as ref

    ops = dict.fromkeys(ref.FLOAT32["operands"], "bfloat16")
    return {"float32": ref.FLOAT32,
            "bf16_operands": {"activations": "float32", "operands": ops}}


def bf16_program(cell: dict) -> dict:
    """The cell with the program computing in bfloat16."""
    cell = copy.deepcopy(cell)
    cell["config"]["model"]["compute_dtype"] = "bfloat16"
    return cell


def _numbers(run, plant, compare_in) -> dict:
    col = run.collect(plant)
    out = {"numbers": run.compare(col, run.cell["config"]["precision"])}
    for name, prec in compare_in.items():
        out[f"numbers_{name}"] = run.compare(col, prec)
    return out


def readings(cell: dict, program_seeds, control_seeds, seconds: float,
             plants=PLANTS, compare_in=()):
    """Yield one dict per reading."""
    from bench.configs import pointcloud_ref as ref

    loop = importlib.import_module(f"bench.kinds.{cell['traffic']['kind']}")
    extra = {k: _extra()[k] for k in compare_in}
    train = cell["traffic"]["kind"] == "train"
    plants = [k for k in plants if train or k != "fault_half_batch"]

    def program(kind, c, seed):
        t0 = time.perf_counter()
        try:
            run = loop.Run(c, seed)
            rec = run.window(seconds)
            out = {**_numbers(run, None, extra), "attempted": rec["attempted"]}
        except Exception as e:              # a control that crashes has failed
            if kind == "program":
                raise
            out = {"error": f"{type(e).__name__}: {e}"[:2000]}
        return {"kind": kind, "seed": seed, **out,
                "seconds": time.perf_counter() - t0}

    def reference(kind, plant, seed):
        t0 = time.perf_counter()
        run = loop.Run(cell, seed, with_program=False)
        return {"kind": kind, "seed": seed, **_numbers(run, plant, extra),
                "seconds": time.perf_counter() - t0}

    for seed in program_seeds:
        yield program("program", cell, seed)
    for seed in control_seeds:
        for kind in plants:
            if kind == "control_bf16_program":
                yield program(kind, bf16_program(cell), seed)
            elif kind == "control_bf16_reference":
                yield reference(kind, {"precision": ref.BFLOAT16}, seed)
            else:
                yield reference(kind, {"drop_half": True}, seed)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--plants", nargs="*", default=list(PLANTS), choices=PLANTS)
    ap.add_argument("--compare-in", nargs="*", default=[],
                    choices=["float32", "bf16_operands"])
    args = ap.parse_args()
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import run

    bad = run.off_kernel_overrides()
    if bad:
        run.refuse("set in the environment: " + "; ".join(bad))
    cell = run.load_cell(args.workload)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.refuse(f"no TPU: JAX sees {dev.platform}")
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for r in readings(cell, args.program_seeds, args.control_seeds,
                      args.seconds, args.plants, args.compare_in):
        print(json.dumps({"workload": args.workload, **r}), flush=True)


if __name__ == "__main__":
    main()
