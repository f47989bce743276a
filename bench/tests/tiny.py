"""A cell of each kind at a size the CPU holds: the harness, the reference
and the checks run as on the chip, with the kernels in interpret mode."""

from __future__ import annotations

import copy
import json
from pathlib import Path

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"

MODEL = {"n_layers": 2, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 8, "d_ff": 64}
BSA = {"ball_size": 64, "top_k": 2}


def config(name: str = "shapenet-bsa") -> dict:
    """The configuration at this size.  Its precision is the CPU's: there
    the program's matmuls multiply float32 operands."""
    from bench.configs import pointcloud_ref as ref

    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg["model"].update(MODEL)
    cfg["bsa"].update(BSA)
    cfg["precision"] = copy.deepcopy(ref.FLOAT32)
    return cfg


def cell(config_name: str, traffic: str) -> dict:
    t = json.loads((TRAFFIC / f"{traffic}.json").read_text())
    if t["kind"] == "train":
        t.update(batch=2, pool=6, points=200, pad_to=256)
    else:
        k = min(t["clouds_per_request"], 4)
        t.update(clouds_per_request=k, batch_slots=max(k // 2, 1),
                 pad_to=256 * max(k // 2, 1), pad_cloud=256, points=200,
                 pool=4, warmup_requests=1, check_requests=2)
    return {"name": f"tiny.{config_name}.{traffic}", "chips": 1,
            "config": config(config_name), "traffic": copy.deepcopy(t),
            "end_to_end": [], "per_layer": []}
