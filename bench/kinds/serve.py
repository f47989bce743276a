"""Serving traffic: one closed-loop client of ``GeometryEngine.predict``.

Each request is ``clouds_per_request`` clouds drawn from a pool made in
set-up (every point of a pool cloud, with fresh jitter), sent when the
previous reply is in host memory.  A request's latency runs from
its send to its last prediction in host memory.  Once the window has closed,
a sample of its requests drawn from the seed, the one with the most points
among them, is checked cloud by cloud against the reference.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import compare, flops, program
from bench.configs import pointcloud_ref as ref
from bench.traffic import generator


class Run:
    """One run of a serving cell.  ``with_program=False`` makes the inputs
    alone, for a control that puts the reference in the program's place."""

    def __init__(self, cell: dict, seed: int, *, with_program: bool = True):
        self.cell, self.seed = cell, seed
        t = cell["traffic"]
        self.pool = generator.cloud_pool(seed, t["pool"], t["points"])
        self.replies = {}
        if with_program:
            from repro.serving.engine import GeometryEngine

            self.api = program.model_api(cell["config"])
            params = jax.jit(self.api.init)(jax.random.PRNGKey(seed))
            self.engine = GeometryEngine(self.api, params,
                                         batch_slots=t["batch_slots"],
                                         layout=t["layout"], pad_to=t["pad_to"])
            for i in range(t["warmup_requests"]):
                self.engine.predict(self.request(i))

    def request(self, i: int) -> list:
        t = self.cell["traffic"]
        k = t["clouds_per_request"]
        return [generator.request_cloud(self.pool, self.seed, i * k + c,
                                        t["jitter"]) for c in range(k)]

    def window(self, seconds: float) -> dict:
        latencies, points, failed = [], 0, 0
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            while time.perf_counter() - t0 < seconds:
                i = len(latencies)
                with TraceAnnotation("bench.request_gen"):
                    clouds = self.request(i)
                sent = time.perf_counter()
                with TraceAnnotation("bench.request"):
                    preds = self.engine.predict(clouds)
                latencies.append(time.perf_counter() - sent)
                points += sum(len(p) for p, _ in clouds)
                failed += not all(
                    np.isfinite(y).all() and y.shape[0] == len(p)
                    for y, (p, _) in zip(preds, clouds))
                self.replies[i] = preds
        wall = time.perf_counter() - t0
        lat = np.asarray(latencies)
        print(f"[bench] latency ms: median {np.median(lat) * 1e3:.2f}, "
              f"slowest {lat.max() * 1e3:.2f}, over twice the median "
              f"{int((lat > 2 * np.median(lat)).sum())}", file=sys.stderr)
        return {"seconds": wall, "attempted": len(lat), "failed": failed,
                "points": points, "serve_points_per_s": points / wall,
                "serve_latency_p95_ms": float(np.percentile(lat, 95)) * 1e3}

    def work(self, requests: int) -> dict:
        """Required operations and bytes of the first ``requests`` requests."""
        cfg = self.cell["config"]
        pts = [len(p) for i in range(requests) for p, _ in self.request(i)]
        return {"model_flops": flops.model_flops(cfg, pts, train=False),
                "kernels": flops.kernel_work(cfg, pts, train=False,
                                             layout=self.cell["traffic"]["layout"])}

    def sample(self) -> list[int]:
        """The requests checked: a seeded sample of those answered, with the
        one holding the most points in it."""
        t = self.cell["traffic"]
        done = sorted(self.replies)
        size = lambda i: sum(len(p) for p, _ in self.request(i))
        biggest = max(done, key=size)
        rng = np.random.default_rng((self.seed, 4))
        rest = [i for i in rng.permutation(done) if i != biggest]
        return [biggest] + [int(i) for i in rest[:t["check_requests"] - 1]]

    def check(self, plant: dict | None = None) -> dict:
        """The numbers compared with the reference in the configuration's
        precision, after the program's state is freed (``collect``)."""
        return self.compare(self.collect(plant), self.cell["config"]["precision"])

    def collect(self, plant: dict | None = None) -> dict:
        """What the comparison needs, with the program's state freed: the
        sampled clouds, what the timed path answered, and the block ids its
        selection pass chose for them.  The selection pass is the engine's
        ``predict(..., return_selection=True)`` on the sampled requests;
        ``pass_gap`` holds it to the timed answers.  ``plant``:
        ``{"precision": p}``, a precision (``pointcloud_ref.resolve``) in
        which the reference, put in the program's place, answers the sample
        instead, as a control."""
        t, cfg = self.cell["traffic"], self.cell["config"]
        bsa = cfg["model"]["attention"] == "bsa"
        k = t["clouds_per_request"]
        if plant is not None:
            self.replies = {i: None for i in range(t["check_requests"])}
        picked = self.sample()
        clouds = [c for i in picked for c in self.request(i)]
        got = [y for i in picked for y in (self.replies[i] or [])]
        ids, pass_gap = [None] * len(clouds), None
        if plant is None and bsa:
            pass_gap = 0.0
            for j, i in enumerate(picked):
                again, sels = self.engine.predict(self.request(i),
                                                  return_selection=True)
                ids[j * k:(j + 1) * k] = [s["indices"] for s in sels]
                pass_gap = max([pass_gap] + [compare.pred_gap(a, b) for a, b in
                                             zip(again, got[j * k:(j + 1) * k])])
        self.__dict__.pop("engine", None)
        self.replies = {}
        if plant is not None:
            with jax.default_matmul_precision("highest"):
                params = ref.init(self.seed, cfg)
                low = jax.jit(lambda p, f, m, s: ref.forward(
                    p, f, m, cfg, s, plant["precision"]))
                got, ids = [], []
                for pts, feats in clouds:
                    y, i_c = _reference(low, params, cfg, pts, feats,
                                        t["pad_cloud"], None)[:2]
                    got.append(y)
                    ids.append(i_c)
        return {"clouds": clouds, "got": got, "ids": ids, "pass_gap": pass_gap}

    def compare(self, col: dict, precision: dict) -> dict:
        """``collect``'s answers against the reference in ``precision``."""
        t, cfg = self.cell["traffic"], self.cell["config"]
        bsa = cfg["model"]["attention"] == "bsa"
        out = {"prediction_gap": 0.0, "prediction_rms_gap": 0.0}
        if bsa:
            out["selection_gap"] = -np.inf
        if col["pass_gap"] is not None:
            out["selection_pass_gap"] = col["pass_gap"]
        with jax.default_matmul_precision("highest"):
            params = ref.init(self.seed, cfg)
            fwd = jax.jit(lambda p, f, m, s: ref.forward(p, f, m, cfg, s, precision))
            for (pts, feats), y, i_c in zip(col["clouds"], col["got"], col["ids"]):
                want, _, gap = _reference(fwd, params, cfg, pts, feats,
                                          t["pad_cloud"], i_c)
                out["prediction_gap"] = max(out["prediction_gap"],
                                            compare.pred_gap(y, want))
                out["prediction_rms_gap"] = max(out["prediction_rms_gap"],
                                                compare.rms_gap(y, want))
                if bsa:
                    out["selection_gap"] = max(out["selection_gap"], gap)
        return out


def _reference(fwd, params, cfg, pts, feats, pad, ids):
    """One cloud through the reference: its own ball order, padded to
    ``pad`` points; ``ids`` (n_layers, G_cloud, Hkv, k*) replays a run's
    selection (-1 rows: own choice).  Returns (predictions in the caller's
    point order, the cloud's ids, largest replay gap)."""
    b = cfg["bsa"]
    perm = generator.ball_order(pts, b["ball_size"])
    n = len(pts)
    f = np.zeros((pad, feats.shape[1]), np.float32)
    f[:n] = feats[perm]
    mask = np.arange(pad) < n
    select = None
    if ids is not None:
        ids = np.asarray(ids)
        select = np.full(ids.shape[:1] + (pad // b["group_size"],) + ids.shape[2:],
                         -1, np.int32)
        select[:, :ids.shape[1]] = ids
    y, got_ids, gap = fwd(params, jnp.asarray(f), jnp.asarray(mask), select)
    y = np.asarray(y, np.float32)
    out = np.empty((n, y.shape[1]), np.float32)
    out[perm] = y[:n]
    g_cloud = -(-n // b["ball_size"]) * b["ball_size"] // b["group_size"]
    cloud_ids = None
    if cfg["model"]["attention"] == "bsa":
        cloud_ids = np.asarray(got_ids)[:, :g_cloud]
    return out, cloud_ids, float(gap)
