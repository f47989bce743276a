#!/usr/bin/env python3
"""Drive the shapenet-bsa training and inference path once on a TPU chip.

    python chip_smoke.py               # one chip: train, then serve
    python chip_smoke.py --chips 4     # four chips: context parallelism only

One chip: the paper model exactly as ``configs/shapenet_bsa.py`` defines it
(18 layers, d_model 256, 8 heads x 32, d_ff 1024, fp32, remat, ball 256,
l 8, top-k 4, group 8) on the ``pallas`` backend trains 5 steps through
``runtime.Trainer`` at the launchers' default matmul precision, with random
weights from ``--seed`` and the synthetic ShapeNet-Car set (3586 points
padded to 3840, batch 4).  The compiled step must launch the ball,
compression (flash), selection and gated-epilogue kernels, every loss must
be finite, and step 1's loss and gradient norm must match the ``jnp``
reference backend.  Then ``serving.GeometryEngine`` answers 8 ragged clouds
(2800-3586 points) in its packed-varlen layout, checked cloud by cloud
against the ``jnp`` backend run on each cloud alone.

Four chips (``--chips 4``): one 32768-point cloud through ``bsa_attention``
and one 32768-token causal sequence (``LM_BSA``) through
``nsa_causal_attention``, forward and backward, on the ``"sharded"``
backend over a 4-chip mesh, against the same calls on one chip.  A
sharded-backend fallback warning is an error there.

The ``jnp`` reference always runs under ``jax.default_matmul_precision
("highest")``.  A comparison passes when |got - want| <= TOL + TOL * |want|
holds element by element, with TOL from the kernel parity suite
(``tests/test_kernels_grad.py``): 1e-3, its fp32 tier, for the program run
at "highest" precision too (a second step-1 and serving pass, and both
sides of the four-chip comparison); 6e-2, its widest bf16 tier, for the
program at the default precision, whose TPU matmuls round their operands
to bfloat16.  Selection is a discrete top-k, so two correct runs can break
a near-tie between candidate blocks differently and then disagree far
beyond rounding; the serving reference therefore REPLAYS the engine's
selected blocks, and must find them a top-k of its own scores up to the
same TOL (``core.bsa._select_blocks``: ``gap``).

Exits non-zero, printing no result, when JAX finds no TPU, when an
environment override would take the run off the compiled kernels, or when
any phase fails.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Compiled programs are cached where ``$JAX_COMPILATION_CACHE_DIR`` says, else
in ``<checkout>/.jax_cache``; the second run in a checkout loads them.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
# tolerance tiers of tests/test_kernels_grad.py: fp32, and bf16 (widest)
TOL_HIGHEST, TOL_DEFAULT = 1e-3, 6e-2
STEPS, BATCH = 5, 4
N_CLOUDS, CLOUD_POINTS = 8, (2800, 3586)
CP_TOKENS = 32768                  # four-chip cloud / causal sequence length
# every kernel the shapenet-bsa train step must launch, forward and backward
TRAIN_KERNELS = ("bsa_ball_fwd", "bsa_ball_bwd", "bsa_flash_fwd",
                 "bsa_flash_dq", "bsa_flash_dkv", "bsa_selection_fwd",
                 "bsa_selection_bwd", "bsa_epilogue_fwd", "bsa_epilogue_bwd")


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def refuse(msg: str) -> None:
    print(f"[chip_smoke] refusing to run: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def off_kernel_overrides() -> list[str]:
    """Environment settings under which the run would not measure the
    compiled kernels with tiles chosen by the code."""
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


def check_close(name: str, got, want, tol: float) -> None:
    """Elementwise |got - want| <= tol + tol·|want| (numpy.allclose)."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = np.abs(got - want)
    share = float(np.max(diff / (tol + tol * np.abs(want))))
    log(f"{name}: max|diff| {diff.max():.3e}, worst element at {share:.4f} "
        f"of the tolerance {tol:g}")
    if got.shape != want.shape or not share <= 1.0:
        raise SmokeFailure(f"{name} differs from the reference "
                           f"({got.shape} vs {want.shape}, {share:.3f})")


def launched_kernels(hlo_text: str) -> set[str]:
    """Names of the Mosaic kernels a compiled program launches."""
    return {line.split("=")[0].strip().lstrip("%").rsplit(".", 1)[0]
            for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


# ---------------------------------------------------------------------------
# one chip: train, then serve
# ---------------------------------------------------------------------------

def with_backend(mcfg, backend: str):
    import dataclasses
    return mcfg.scaled(bsa=dataclasses.replace(mcfg.bsa, backend=backend))


def train_phase(jax, seed: int):
    import itertools

    import numpy as np

    from repro.configs import get_config
    from repro.data import ShapeNetCarDataset
    from repro.models.api import model_api
    from repro.optim.clip import global_norm
    from repro.runtime import Trainer, TrainerConfig

    mcfg = with_backend(get_config("shapenet-bsa"), "pallas")
    api, ref_api = model_api(mcfg), model_api(with_backend(mcfg, "jnp"))
    log(f"model {mcfg.name}: {mcfg.n_layers} layers, d_model {mcfg.d_model}, "
        f"{mcfg.n_heads}x{mcfg.head_dim} heads, d_ff {mcfg.d_ff}, "
        f"{mcfg.param_dtype}, remat={mcfg.remat}, bsa={mcfg.bsa}")

    t0 = time.perf_counter()
    ds = ShapeNetCarDataset("train")
    batches = list(itertools.islice(ds.batches(BATCH, seed=seed), STEPS))
    log(f"data: {STEPS} batches {batches[0]['feats'].shape} "
        f"({ds.n_points} points padded to {batches[0]['feats'].shape[1]}) "
        f"made in {time.perf_counter() - t0:.1f} s")

    tr = Trainer(api, TrainerConfig(total_steps=STEPS, warmup_steps=1,
                                    log_every=1, seed=seed))
    params, opt_state = tr.init_state()

    t0 = time.perf_counter()
    compiled = tr.lower_step(params, opt_state, batches[0]).compile()
    log(f"train step compile (default precision): "
        f"{time.perf_counter() - t0:.1f} s")
    kernels = launched_kernels(compiled.as_text())
    log(f"train step launches {sorted(kernels)}")
    missing = sorted(set(TRAIN_KERNELS) - kernels)
    if missing:
        raise SmokeFailure(f"compiled train step lacks kernels {missing}")

    # step 1 at "highest", before fit donates the initial parameters
    step1 = {}
    with jax.default_matmul_precision("highest"):
        for name, a in (("jnp", ref_api), ("pallas", api)):
            (loss, _), grads = jax.jit(jax.value_and_grad(
                a.loss, has_aux=True))(params, batches[0])
            step1[name] = (float(loss), float(global_norm(grads)))

    params, opt_state = tr.fit(iter(batches), steps=STEPS, params=params,
                               opt_state=opt_state)
    hist = tr.metrics_history
    losses = [m["loss"] for m in hist]
    norms = [m["grad_norm"] for m in hist]
    if len(hist) != STEPS or not all(map(np.isfinite, losses + norms)):
        raise SmokeFailure(f"train losses {losses}, grad norms {norms}")
    log(f"losses {losses}")
    log(f"step time (informational): median of steps 2-{STEPS} "
        f"{statistics.median(m['step_time_s'] for m in hist[1:]) * 1e3:.1f} ms"
        f", step 1 (incl. dispatch compile) {hist[0]['step_time_s']:.2f} s")

    ref_loss, ref_norm = step1["jnp"]
    check_close("step-1 loss, default precision, vs jnp", losses[0],
                ref_loss, TOL_DEFAULT)
    check_close("step-1 grad norm, default precision, vs jnp", norms[0],
                ref_norm, TOL_DEFAULT)
    check_close("step-1 loss, highest precision, vs jnp", step1["pallas"][0],
                ref_loss, TOL_HIGHEST)
    check_close("step-1 grad norm, highest precision, vs jnp",
                step1["pallas"][1], ref_norm, TOL_HIGHEST)
    return mcfg, params


def serve_phase(jax, mcfg, params) -> None:
    import contextlib

    import numpy as np

    from repro.data import ShapeNetCarDataset
    from repro.models.api import model_api
    from repro.serving.engine import GeometryEngine

    ds = ShapeNetCarDataset("test", n_points_range=CLOUD_POINTS)
    clouds = []
    for i in range(N_CLOUDS):
        item = ds[i]
        feats = item["feats"][item["mask"]]           # real points only
        clouds.append((feats[:, :3], feats))
    log(f"serving {N_CLOUDS} clouds of {[len(f) for _, f in clouds]} points")

    # the reference: the jnp backend on one cloud at a time, bucket-padded
    ref_eng = GeometryEngine(model_api(with_backend(mcfg, "jnp")), params,
                             batch_slots=1, layout="padded",
                             pad_to=ds.max_padded_len)

    for precision, tol in (("default", TOL_DEFAULT),
                           ("highest", TOL_HIGHEST)):
        eng = GeometryEngine(model_api(mcfg), params, batch_slots=N_CLOUDS)
        if eng.layout != "packed":
            raise SmokeFailure(f"GeometryEngine chose layout {eng.layout!r}")
        scope = (contextlib.nullcontext() if precision == "default" else
                 jax.default_matmul_precision(precision))
        t0 = time.perf_counter()
        with scope:
            preds, sels = eng.predict(clouds, return_selection=True)
        log(f"engine (packed varlen, pallas, {precision} precision): "
            f"{time.perf_counter() - t0:.1f} s incl. compile")
        # the same reference program once with every row on its own top-k
        # (−1), once replaying the engine's blocks
        own_rows = [{"indices": np.full_like(s["indices"], -1)} for s in sels]
        with jax.default_matmul_precision("highest"):
            own = ref_eng.predict(clouds, select=own_rows)[0]
            refs, replay = ref_eng.predict(clouds, select=sels)
        for i, ((_, f), got, want, rp, mine) in enumerate(
                zip(clouds, preds, refs, replay, own)):
            if (got.shape != (len(f), mcfg.out_dim)
                    or not np.isfinite(got).all()):
                raise SmokeFailure(f"cloud {i}: prediction {got.shape}, "
                                   f"finite={np.isfinite(got).all()}")
            gap = float(rp["gap"].max())
            log(f"cloud {i} ({len(f)} points, {precision}): the reference "
                f"would pick other blocks in {int(rp['flips'].sum())} "
                f"(group, head, layer) rows, largest score gap {gap:.3e}; "
                f"without the replay max|diff| "
                f"{np.abs(got - mine).max():.3e}")
            if not gap <= tol:
                raise SmokeFailure(f"cloud {i}: the engine's selected blocks "
                                   f"are no top-k of the reference's scores "
                                   f"(gap {gap:.3e} > {tol:g})")
            check_close(f"cloud {i} ({precision}) vs per-cloud jnp", got,
                        want, tol)


# ---------------------------------------------------------------------------
# four chips: context parallelism on the sharded backend
# ---------------------------------------------------------------------------

def context_parallel_phase(jax, seed: int) -> None:
    import dataclasses

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs.presets import LM_BSA, PAPER_BSA
    from repro.core import (bsa_attention, bsa_init, get_backend,
                            nsa_causal_attention, nsa_init)
    from repro.distributed import mesh_context
    from repro.launch.mesh import make_local_mesh

    # the sharded backend's inner backend is "auto" (REPRO_SHARDED_INNER is
    # refused above), which must resolve to the compiled kernels here
    if get_backend("auto").name != "pallas":
        raise SmokeFailure("'auto' does not resolve to the pallas backend")
    mesh = make_local_mesh(4)
    seq = NamedSharding(mesh, P(None, "data"))
    one = jax.devices()[0]
    cases = [
        # shapenet-bsa attention widths on one large cloud
        ("cloud", bsa_attention, bsa_init, PAPER_BSA, 8, 8, 32, 256),
        # a GQA causal sequence (4 query heads per KV head) at LM_BSA
        ("causal", nsa_causal_attention, nsa_init, LM_BSA, 8, 2, 128, 1024),
    ]
    for name, attend, init, cfg, hq, hkv, d, d_model in cases:
        keys = jax.random.split(jax.random.PRNGKey(seed), 5)
        n = CP_TOKENS
        params = init(keys[0], dataclasses.replace(cfg, backend="pallas"),
                      n_heads=hq, n_kv_heads=hkv, head_dim=d, d_model=d_model)
        q = jax.random.normal(keys[1], (1, n, hq, d))
        k = jax.random.normal(keys[2], (1, n, hkv, d))
        v = jax.random.normal(keys[3], (1, n, hkv, d))
        ct = jax.random.normal(keys[4], (1, n, hq, d))  # output cotangent

        def fwd_bwd(params, q, k, v, ct, cfg):
            out, vjp = jax.vjp(lambda *a: attend(*a, cfg=cfg), params, q, k, v)
            return out, vjp(ct)

        results = {}
        for backend in ("pallas", "sharded"):
            bcfg = dataclasses.replace(cfg, backend=backend)
            step = jax.jit(lambda *a, bcfg=bcfg: fwd_bwd(*a, bcfg))
            if backend == "sharded":
                args = [jax.device_put(params, NamedSharding(mesh, P()))] + [
                    jax.device_put(t, seq) for t in (q, k, v, ct)]
            else:
                args = jax.device_put((params, q, k, v, ct), one)
            scope = (mesh_context(mesh) if backend == "sharded"
                     else contextlib.nullcontext())
            t0 = time.perf_counter()
            with scope, warnings.catch_warnings():
                warnings.simplefilter("error", RuntimeWarning)   # fallbacks
                out, grads = jax.block_until_ready(step(*args))
            log(f"{name} ({n} tokens) {backend}: "
                f"{time.perf_counter() - t0:.1f} s incl. compile")
            results[backend] = (out, grads)

        out, grads = results["sharded"]
        for label, arr in (("output", out), ("dq", grads[1])):
            devs = {s.device for s in arr.addressable_shards}
            rows = {s.data.shape[1] for s in arr.addressable_shards}
            if len(devs) != 4 or rows != {n // 4}:
                raise SmokeFailure(f"{name} sharded {label}: {len(devs)} "
                                   f"devices, per-shard rows {rows}")
        log(f"{name}: output and dq split over 4 devices, {n // 4} rows each")
        want_out, want_grads = results["pallas"]
        check_close(f"{name} sharded output vs one chip", out, want_out,
                    TOL_HIGHEST)
        for path, g, w in zip(
                [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(grads)[0]],
                jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
            check_close(f"{name} sharded grad{path} vs one chip", g, w,
                        TOL_HIGHEST)


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve on one chip; 4: the context-"
                         "parallel phase over a 4-chip mesh, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    bad = off_kernel_overrides()
    if bad:
        refuse("set in the environment: " + "; ".join(bad))

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        refuse(f"no TPU: JAX sees {len(devices)} {dev.platform} device(s)")
    if len(devices) < args.chips:
        refuse(f"--chips {args.chips} needs {args.chips} TPUs, found "
               f"{len(devices)}")
    log(f"jax {jax.__version__}, {dev.device_kind}, {len(devices)} device(s)")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    cache_hits = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)

    t0 = time.perf_counter()
    if args.chips == 1:
        mcfg, params = train_phase(jax, args.seed)
        serve_phase(jax, mcfg, params)
    else:
        with jax.default_matmul_precision("highest"):
            context_parallel_phase(jax, args.seed)
    log(f"compile cache {cache_dir}: {len(cache_hits)} hit(s); "
        f"all phases {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
