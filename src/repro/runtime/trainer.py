"""Production training loop: jit'd train_step with sharded state, periodic
async checkpoints, preemption-safe save (SIGTERM), straggler watchdog,
resume / elastic restart.

The same Trainer drives the paper's point-cloud training and the LM archs
(everything routes through ``models.api.model_api``).
"""

from __future__ import annotations

import dataclasses
import signal
import time

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.checkpoint import CheckpointManager, latest_step
from repro.distributed.params import batch_shardings, opt_shardings, param_shardings
from repro.distributed.sharding import axis_rules
from repro.launch.steps import make_train_step
from repro.optim import adamw_init
from repro.runtime.watchdog import Watchdog


@dataclasses.dataclass
class TrainerConfig:
    base_lr: float = 1e-3
    weight_decay: float = 0.01
    total_steps: int = 100_000
    warmup_steps: int = 1000
    max_grad_norm: float = 1.0
    ckpt_dir: str | None = None
    ckpt_every: int = 500
    keep_last: int = 3
    log_every: int = 50
    seed: int = 0


class Trainer:
    def __init__(self, api, cfg: TrainerConfig, *, mesh=None, rules=None):
        self.api = api
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules or {}
        self.watchdog = Watchdog().start()
        self.ckpt = (CheckpointManager(cfg.ckpt_dir, keep_last=cfg.keep_last)
                     if cfg.ckpt_dir else None)
        self._preempted = False
        self.metrics_history: list[dict] = []

        step_fn = make_train_step(
            api, base_lr=cfg.base_lr, weight_decay=cfg.weight_decay,
            total_steps=cfg.total_steps, warmup_steps=cfg.warmup_steps,
            max_grad_norm=cfg.max_grad_norm)

        if mesh is not None:
            pstruct = jax.eval_shape(api.init, jax.random.PRNGKey(cfg.seed))
            ostruct = jax.eval_shape(
                lambda p: adamw_init(p, state_dtype=jnp.dtype(api.mcfg.opt_state_dtype)),
                pstruct)
            self.p_sh = param_shardings(pstruct, mesh, zero1=api.mcfg.fsdp)
            self.o_sh = opt_shardings(ostruct, mesh)
            self._jit_step = jax.jit(step_fn, in_shardings=(self.p_sh, self.o_sh, None),
                                     donate_argnums=(0, 1))
        else:
            self.p_sh = self.o_sh = None
            self._jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    # ------------------------------------------------------------------

    def init_state(self):
        with self._ctx():
            params = jax.jit(self.api.init)(jax.random.PRNGKey(self.cfg.seed))
            if self.p_sh is not None:
                params = jax.device_put(params, self.p_sh)
            opt_state = adamw_init(
                params, state_dtype=jnp.dtype(self.api.mcfg.opt_state_dtype))
            if self.o_sh is not None:
                opt_state = jax.device_put(opt_state, self.o_sh)
        return params, opt_state

    def lower_step(self, params, opt_state, batch):
        """The jitted step ``fit`` runs, lowered for these arguments (AOT:
        ``.compile()`` gives the executable, whose ``.as_text()`` shows
        which kernels the step launches)."""
        with self._ctx():
            return self._jit_step.lower(params, opt_state, batch)

    def _ctx(self):
        if self.mesh is not None:
            return axis_rules(self.mesh, self.rules)
        import contextlib
        return contextlib.nullcontext()

    def maybe_restore(self, params, opt_state):
        """Resume from the newest checkpoint if one exists (elastic: the
        target shardings may correspond to a different mesh than at save)."""
        if self.ckpt is None or latest_step(self.cfg.ckpt_dir) is None:
            return params, opt_state, 0
        state, meta = self.ckpt.restore(
            {"params": params, "opt": opt_state},
            shardings=({"params": self.p_sh, "opt": self.o_sh}
                       if self.p_sh is not None else None))
        return state["params"], state["opt"], meta["step"]

    def _install_sigterm(self, get_state):
        def handler(signum, frame):
            self._preempted = True
        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass  # non-main thread (tests)

    # ------------------------------------------------------------------

    def fit(self, batches, *, steps: int, params=None, opt_state=None,
            start_step: int | None = None):
        """Run ``steps`` optimizer steps over ``batches`` (iterator of pytrees)."""
        if params is None:
            params, opt_state = self.init_state()
            params, opt_state, restored = self.maybe_restore(params, opt_state)
        else:
            restored = 0
        step0 = restored if start_step is None else start_step
        self._install_sigterm(lambda: (params, opt_state))

        it = iter(batches)
        t_train0 = time.time()
        for step in range(step0, step0 + steps):
            # each step is a profiler step (the step-time view reads it);
            # its children split the host's part from the device's
            with StepTraceAnnotation("repro.trainer.step", step_num=step):
                with TraceAnnotation("repro.trainer.batch"):
                    batch = next(it)
                    state = batch.pop("_state", None)
                    if self.mesh is not None:
                        b_sh = batch_shardings(
                            jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                                x.shape, x.dtype), batch), self.mesh)
                        batch = jax.device_put(batch, b_sh)
                t0 = time.time()
                with TraceAnnotation("repro.trainer.dispatch"), self._ctx():
                    params, opt_state, metrics = self._jit_step(
                        params, opt_state, batch)
                with TraceAnnotation("repro.trainer.wait"):
                    jax.block_until_ready(metrics["loss"])
                dt = time.time() - t0
                self.watchdog.step(step, dt)

                if step % self.cfg.log_every == 0 or step == step0 + steps - 1:
                    with TraceAnnotation("repro.trainer.log"):
                        m = {k: float(v) for k, v in metrics.items()}
                        m.update(step=step, step_time_s=round(dt, 4))
                        self.metrics_history.append(m)
                        print(f"step {step:6d}  loss {m['loss']:.4f}  "
                              f"gnorm {m.get('grad_norm', 0):.2f}  "
                              f"{dt*1e3:.0f} ms", flush=True)
                if self.ckpt and (step % self.cfg.ckpt_every == 0
                                  or self._preempted
                                  or step == step0 + steps - 1) and step > step0:
                    with TraceAnnotation("repro.trainer.checkpoint"):
                        self.ckpt.save(
                            step, {"params": params, "opt": opt_state},
                            extra={"data_state": state} if state else None,
                            block=self._preempted)
                    if self._preempted:
                        print(f"preempted: state saved at step {step}",
                              flush=True)
                        break
        self.watchdog.stop()
        if self.ckpt:
            self.ckpt.wait()
        self.wall_time = time.time() - t_train0
        return params, opt_state
