"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 100 --batch 8 --seq 256 --smoke          # CPU-sized
    python -m repro.launch.train --arch shapenet-bsa --steps 1000

On a real TPU pod slice this is the per-host entry point: jax.distributed
initializes from the TPU environment, the mesh comes from
``make_production_mesh()``, and every host feeds its local batch shard.
On CPU it runs single-process (optionally with a small fake mesh).
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.configs.reduce import smoke_config
from repro.data import ShapeNetCarDataset, lm_batches
from repro.models.api import model_api
from repro.runtime import Trainer, TrainerConfig
from repro.runtime.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--backend", default=None,
                    help="attention backend: jnp | pallas | interpret | auto "
                         "| any registered plug-in (default: config)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--mesh", default="", help="e.g. 2x4 → (data=2, model=4)")
    ap.add_argument("--distributed", action="store_true",
                    help="jax.distributed.initialize() from TPU env")
    args = ap.parse_args()
    use_compile_cache()

    if args.distributed:
        jax.distributed.initialize()

    mcfg = get_config(args.arch)
    if args.smoke:
        mcfg = smoke_config(mcfg)
    if args.backend:
        import dataclasses
        mcfg = mcfg.scaled(bsa=dataclasses.replace(mcfg.bsa, backend=args.backend))
    api = model_api(mcfg)

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        dims = tuple(int(x) for x in args.mesh.split("x"))
        names = ("data", "model")[:len(dims)]
        mesh = make_mesh(dims, names)

    cfg = TrainerConfig(base_lr=args.lr, total_steps=args.steps,
                        warmup_steps=max(args.steps // 20, 1),
                        ckpt_dir=args.ckpt, log_every=max(args.steps // 20, 1))
    trainer = Trainer(api, cfg, mesh=mesh)

    if mcfg.family == "pointcloud":
        data = ShapeNetCarDataset("train").batches(args.batch, seed=0)
    else:
        data = lm_batches(vocab_size=mcfg.vocab_size, batch_size=args.batch,
                          seq_len=args.seq, seed=0)
    trainer.fit(data, steps=args.steps)
    print(f"done: {args.steps} steps, wall {trainer.wall_time:.1f}s, "
          f"stragglers {len(trainer.watchdog.straggler_events)}")


if __name__ == "__main__":
    main()
