"""Training traffic: ``Trainer.fit`` on batches of synthetic clouds.

Set-up builds one ``Trainer`` with its state from the seed and drives it
through its first steps with the window's own call (``fit``) and feed, on
rows that all differ; the window then calls ``fit`` on that same object for
as many steps as fill it.  The first steps are what the reference follows:
their losses, the first gradient (read back from the optimizer's first
moment) and the parameters' change over them.
"""

from __future__ import annotations

import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import compare, flops, program
from bench.configs import pointcloud_ref as ref
from bench.traffic import generator

ADAM_B1 = 0.9          # the program's AdamW first-moment decay


def leaf_norms(tree) -> dict:
    """{leaf path: float32 norm} of a pytree, computed on its device."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = _norms([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(v) for (p, _), v in zip(flat, norms)}


@jax.jit
def _norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in leaves]


@jax.jit
def _diff(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def _copy(tree):
    return jax.tree.map(lambda x: jnp.array(x, copy=True), tree)


class Feed:
    """The batches, cycled; the benchmark's span marks each hand-off."""

    def __init__(self, batches):
        self.batches = batches
        self.it = itertools.cycle(batches)

    def __iter__(self):
        return self

    def __next__(self):
        with TraceAnnotation("bench.batch"):
            return dict(next(self.it))


class Run:
    """One run of a training cell.  ``with_program=False`` makes the inputs
    alone, for a control that puts the reference in the program's place."""

    def __init__(self, cell: dict, seed: int, *, with_program: bool = True):
        self.cell, self.seed = cell, seed
        cfg, t = cell["config"], cell["traffic"]
        self.selects = cfg["model"]["attention"] == "bsa"
        self.first = t["first_steps"]
        pool = generator.cloud_pool(seed, t["pool"], t["points"])
        rows = generator.train_rows(pool, cfg["bsa"]["ball_size"], t["pad_to"])
        order = np.random.default_rng((seed, 0)).permutation(len(rows))
        b = t["batch"]
        self.host_batches = [
            {k: np.stack([rows[i][k] for i in order[s:s + b]]) for k in rows[0]}
            for s in range(0, len(rows) - b + 1, b)]
        self.batch_points = [int(x["mask"].sum()) for x in self.host_batches]
        if with_program:
            self._setup_program()

    def _setup_program(self):
        from repro.runtime import Trainer, TrainerConfig

        t = self.cell["traffic"]
        self.api = program.model_api(self.cell["config"])
        self.feed = Feed([jax.device_put(x) for x in self.host_batches])
        self.trainer = Trainer(self.api, TrainerConfig(seed=self.seed,
                                                       **t["trainer"]))
        self.params, self.opt = self.trainer.init_state()
        start = _copy(self.params)
        self.starts = []                 # parameters entering each first step
        losses = []
        for step in range(self.first):
            if self.selects:
                self.starts.append(_copy(self.params))
            self.params, self.opt = self.trainer.fit(
                self.feed, steps=1, params=self.params, opt_state=self.opt,
                start_step=step)
            losses.append(self.trainer.metrics_history[-1]["loss"])
            if step == 0:
                grads = {k: v / (1 - ADAM_B1)
                         for k, v in leaf_norms(self.opt["m"]).items()}
        self.prog = {"losses": losses, "grad_norms": grads,
                     "change_norms": leaf_norms(_diff(self.params, start))}
        del start
        self.step_s = self.trainer.metrics_history[-1]["step_time_s"]

    def window(self, seconds: float) -> dict:
        steps = max(1, round(seconds / self.step_s))
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            self.params, self.opt = self.trainer.fit(
                self.feed, steps=steps, params=self.params, opt_state=self.opt,
                start_step=self.first)
        wall = time.perf_counter() - t0
        n = len(self.host_batches)
        points = [self.batch_points[(self.first + i) % n] for i in range(steps)]
        loss = self.trainer.metrics_history[-1]["loss"]
        return {"seconds": wall, "attempted": steps,
                "failed": 0 if np.isfinite(loss) else steps,
                "points": sum(points),
                "train_points_per_s": sum(points) / wall}

    def work(self, steps: int) -> dict:
        """Required operations and bytes of ``steps`` window steps."""
        cfg = self.cell["config"]
        n = len(self.host_batches)
        pts = [int(m.sum()) for i in range(steps)
               for m in self.host_batches[(self.first + i) % n]["mask"]]
        return {"model_flops": flops.model_flops(cfg, pts, train=True),
                "kernels": flops.kernel_work(cfg, pts, train=True)}

    def check(self, plant: dict | None = None) -> dict:
        """The numbers compared with the reference in the configuration's
        precision, after the program's state is freed (``collect``)."""
        return self.compare(self.collect(plant), self.cell["config"]["precision"])

    def collect(self, plant: dict | None = None) -> dict:
        """What the comparison needs, with the program's state freed: the
        first steps' summary, and the block ids each first step's selection
        pass chose (``forward_selection`` on the parameters entering the
        step).  ``pass_gap`` holds that pass's loss to the timed step's.
        ``plant``: keyword arguments of ``pointcloud_ref.train_steps``
        (``precision``, ``drop_half``) for the reference run in the
        program's place, as a control or a planted fault."""
        cfg, t = self.cell["config"], self.cell["traffic"]
        selects, pass_gap = None, None
        if plant is None and self.selects:
            fwd = jax.jit(self.api.forward_selection)
            selects, pass_gap = [], 0.0
            for p, b, want in zip(self.starts, self.feed.batches[:self.first],
                                  self.prog["losses"]):
                pred, sel = fwd(p, b)
                selects.append(np.asarray(sel["indices"]))
                pass_gap = max(pass_gap, compare.rel_gap(_mse(pred, b), want))
        for name in ("trainer", "params", "opt", "starts", "feed"):
            self.__dict__.pop(name, None)
        if plant is not None:
            plant = {"precision": cfg["precision"], **plant}
            with jax.default_matmul_precision("highest"):
                start = ref.init(self.seed, cfg)
                steps = ref.train_steps(start, self.host_batches[:self.first],
                                        cfg, t["trainer"], **plant)
            # a half batch has no ids for the other half to replay
            selects = ([s["ids"] for s in steps]
                       if self.selects and not plant.get("drop_half") else None)
            self.prog = _summary(start, steps)
        return {"prog": self.prog, "selects": selects, "pass_gap": pass_gap}

    def compare(self, col: dict, precision: dict) -> dict:
        """``collect``'s first steps against the reference in ``precision``."""
        cfg, t = self.cell["config"], self.cell["traffic"]
        with jax.default_matmul_precision("highest"):
            start = ref.init(self.seed, cfg)
            steps = ref.train_steps(start, self.host_batches[:self.first], cfg,
                                    t["trainer"], precision=precision,
                                    selects=col["selects"])
        want = _summary(start, steps)
        want["gaps"] = [s["gap"] for s in steps] if self.selects else None
        out = compare.train_numbers(col["prog"], want)
        if col["pass_gap"] is not None:
            out["selection_pass_gap"] = col["pass_gap"]
        return out


def _mse(pred, batch) -> float:
    """The training loss of predictions ``pred`` for ``batch``."""
    mask = np.asarray(batch["mask"])
    err = (np.asarray(pred, np.float64) - np.asarray(batch["target"], np.float64)) ** 2
    return float(np.where(mask[..., None], err, 0.0).sum() / (mask.sum() * err.shape[-1]))


def _summary(start, steps) -> dict:
    return {"losses": [s["loss"] for s in steps],
            "grad_norms": leaf_norms(steps[0]["grads"]),
            "change_norms": leaf_norms(_diff(steps[-1]["params"], start))}
