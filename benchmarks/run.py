"""Benchmark driver: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines.

  PYTHONPATH=src python -m benchmarks.run [--quick|--full]
  python benchmarks/run.py --smoke     # CI: one tiny fwd+bwd kernel-path iter
"""

import argparse
import sys
import traceback
from pathlib import Path

if __package__ in (None, ""):                    # `python benchmarks/run.py`
    _root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(_root))
    if "repro" not in sys.modules:               # no editable install: use src/
        sys.path.insert(0, str(_root / "src"))


def smoke() -> None:
    """One tiny fwd+bwd iteration through BOTH attention stacks on the Pallas
    kernel path (interpret mode on CPU, compiled on TPU) — proves the
    custom-VJP kernels stay jit-compatible end-to-end.  Exits non-zero on
    NaN/Inf."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import (BSAConfig, bsa_attention, bsa_init,
                            nsa_causal_attention, nsa_init)
    from repro.kernels.common import should_interpret

    B, N, Hq, Hkv, D, dm = 1, 128, 4, 2, 32, 64
    cfg = BSAConfig(ball_size=32, local_window=32, cmp_block=8, slc_block=8,
                    top_k=2, group_size=8, backend="pallas")
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, N, Hq, D))
    k = jax.random.normal(ks[1], (B, N, Hkv, D))
    v = jax.random.normal(ks[2], (B, N, Hkv, D))
    mask = jnp.ones((B, N), bool).at[:, -16:].set(False)

    runs = [
        ("bsa", bsa_init, lambda p: bsa_attention(p, q, k, v, cfg=cfg, mask=mask)),
        ("nsa_causal", nsa_init, lambda p: nsa_causal_attention(p, q, k, v, cfg=cfg)),
    ]
    ok = True
    for name, init, apply in runs:
        params = init(ks[3], cfg, n_heads=Hq, n_kv_heads=Hkv, head_dim=D, d_model=dm)
        step = jax.jit(jax.value_and_grad(lambda p: jnp.sum(apply(p) ** 2)))
        t0 = time.perf_counter()
        loss, grads = step(params)
        jax.block_until_ready((loss, grads))
        dt = time.perf_counter() - t0
        finite = bool(jnp.isfinite(loss)) and all(
            bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
        ok &= finite
        print(f"smoke/{name}_train_step,{dt * 1e6:.1f},"
              f"loss={float(loss):.4f};finite={finite}", flush=True)
    if not ok:
        print("FAILURES: smoke (non-finite loss/grads)")
        sys.exit(1)
    mode = "interpret" if should_interpret() else "compiled"
    print(f"# smoke complete (kernel path fwd+bwd, {mode} mode, "
          f"{jax.devices()[0].platform})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--max-n", type=int, default=4096)
    ap.add_argument("--skip", default="", help="comma list: table1,table2,fig3,appb,roofline")
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny fwd+bwd kernel-path iteration (CI gate)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    skip = set(args.skip.split(","))
    failures = []

    def section(name, fn):
        if name in skip:
            return
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report all benches
            failures.append((name, e))
            traceback.print_exc()

    from benchmarks import appb_ablation, fig3_scaling, table1_shapenet, table2_elasticity
    section("table1+3 (ShapeNet variants)", lambda: table1_shapenet.run(steps=args.steps))
    section("table2 (Elasticity)", lambda: table2_elasticity.run(steps=args.steps))
    section("fig3 (runtime scaling)", lambda: fig3_scaling.run(max_n=args.max_n))
    section("appB (block-size ablation)",
            lambda: appb_ablation.run(steps=max(args.steps // 2, 10),
                                      grid=[(4, 4), (8, 8), (32, 32)]))

    def _roof():
        from benchmarks import roofline
        cells = roofline.load_cells(Path("results/dryrun"))
        if not cells:
            print("# (no dry-run artifacts; run repro.launch.dryrun first)")
            return
        for c in cells:
            print(f"roofline/{c['arch']}/{c['shape']},"
                  f"{max(c['compute_s'], c['memory_s'], c['collective_s'])*1e6:.1f},"
                  f"dom={c['dominant']};frac={c['roofline_fraction']:.3f}")
    section("roofline (from dry-run)", _roof)

    if failures:
        print("FAILURES:", [n for n, _ in failures])
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
