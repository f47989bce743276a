"""End-to-end driver: train the paper's 18-block BSA model on the (synthetic)
ShapeNet-Car airflow-pressure task — checkpointing, watchdog and all.

    PYTHONPATH=src python examples/train_shapenet.py --steps 300 --arch shapenet-bsa

Any Table-3 variant works: shapenet-bsa | shapenet-bsa-no-group |
shapenet-bsa-group-cmp | shapenet-full | shapenet-erwin.

Variable-size geometries: ``--var-points LO HI`` draws every car's point
count from [LO, HI].  The dataset packs the ragged samples into one padded
batch with per-sample masks (pad_to frozen at the range maximum), so the
whole mixed-size batch still runs as ONE jitted train step — no per-sample
Python loop, no shape-churn recompilation.
"""

import argparse


from repro.configs import get_config
from repro.data import ShapeNetCarDataset
from repro.models.api import model_api
from repro.runtime import Trainer, TrainerConfig
from repro.runtime.compile_cache import use_compile_cache


def evaluate(api, params, ds, n_batches=8, batch_size=8, pad_to=None):
    mse, n = 0.0, 0
    import jax, jax.numpy as jnp
    fwd = jax.jit(api.forward)
    for i, batch in enumerate(ds.batches(batch_size, shuffle=False, epochs=1,
                                         pad_to=pad_to)):
        if i >= n_batches:
            break
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        pred = fwd(params, batch)
        m = batch["mask"][..., None]
        mse += float((((pred - batch["target"]) ** 2) * m).sum() / m.sum())
        n += 1
    return mse / max(n, 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="shapenet-bsa")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--layers", type=int, default=0, help="override (0=config)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--backend", default=None,
                    help="attention backend: jnp | pallas | interpret | auto | "
                         "any registered plug-in (default: config; 'pallas' "
                         "trains through the fused custom-VJP kernels — "
                         "interpret mode on CPU, compiled on TPU)")
    ap.add_argument("--use-kernels", action="store_true",
                    help="DEPRECATED: same as --backend pallas")
    ap.add_argument("--var-points", type=int, nargs=2, metavar=("LO", "HI"),
                    default=None,
                    help="ragged geometries: per-sample point counts drawn from "
                         "[LO, HI]; batches are packed + masked (batched path)")
    args = ap.parse_args()
    use_compile_cache()

    mcfg = get_config(args.arch)
    if args.layers:
        mcfg = mcfg.scaled(n_layers=args.layers)
    backend = args.backend
    if args.use_kernels:
        import warnings
        warnings.warn("--use-kernels is deprecated; use --backend pallas",
                      DeprecationWarning)
        backend = backend or "pallas"
    if backend:
        import dataclasses
        mcfg = mcfg.scaled(bsa=dataclasses.replace(mcfg.bsa, backend=backend))
    api = model_api(mcfg)
    nrange = tuple(args.var_points) if args.var_points else None
    train_ds = ShapeNetCarDataset("train", n_points_range=nrange)
    test_ds = ShapeNetCarDataset("test", n_points_range=nrange)
    # freeze the packed length so every mixed-size batch hits ONE compiled step
    pad_to = train_ds.max_padded_len if nrange else None

    cfg = TrainerConfig(base_lr=1e-3, weight_decay=0.01,       # paper App. A
                        total_steps=args.steps, warmup_steps=min(50, args.steps // 10),
                        ckpt_dir=args.ckpt, log_every=20)
    tr = Trainer(api, cfg)
    params, _ = tr.fit(train_ds.batches(args.batch, seed=0, pad_to=pad_to),
                       steps=args.steps)
    mse = evaluate(api, params, test_ds, pad_to=pad_to)
    print(f"\n[{args.arch}] test MSE after {args.steps} steps: {mse:.4f}")
    print(f"wall time {tr.wall_time:.1f}s, stragglers: {len(tr.watchdog.straggler_events)}")


if __name__ == "__main__":
    main()
