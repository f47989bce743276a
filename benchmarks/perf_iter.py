import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf iteration tool: lower ONE (arch × shape) cell with config/sharding
overrides and report the three roofline terms + delta vs the recorded
baseline.  Each hypothesis→change→measure cycle is one invocation.

  PYTHONPATH=src python -m benchmarks.perf_iter --arch stablelm-1.6b \
      --shape train_4k --layout dp --chunk 1024

Overrides:
  --layout {tp,dp}     dp = no tensor parallelism; batch shards over the
                       WHOLE mesh (pod×data×model) and params go ZeRO/FSDP
                       over all axes — the right mapping for small models
  --chunk N            jnp_chunk_tokens override (0 = unchunked)
  --attn-seq           attn_shard_mode=sequence (ball-parallel attention)
  --topk N / --ell N   BSA selection/compression overrides
  --window N           local window override
  --fsdp               force FSDP params
"""

import argparse
import dataclasses
import json
from pathlib import Path

import jax

from repro.configs import SHAPES, get_config
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16


def lower_with_overrides(arch, shape_name, *, mcfg=None, layout="tp",
                         multi_pod=False):
    """Variant of launch.dryrun.lower_cell accepting a modified mcfg/layout."""
    import jax.numpy as jnp
    from repro.distributed.params import (batch_shardings, cache_shardings,
                                          opt_shardings, param_shardings)
    from repro.distributed.sharding import axis_rules
    from repro.launch.dryrun import shape_rules
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
    from repro.models.api import model_api
    from repro.optim import adamw_init

    mcfg = mcfg or get_config(arch)
    shape = SHAPES[shape_name]
    api = model_api(mcfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules, seq_parallel = shape_rules(mcfg, shape, mesh)
    if layout == "dp":
        rules["batch"] = ("pod", "data", "model")
        rules["seq_res"] = None          # no TP ⇒ no Megatron-SP residual
        rules["heads"] = None
        rules["d_ff"] = None
        rules["vocab"] = None
        rules["experts"] = None

    B, N = shape.global_batch, shape.seq_len
    params_struct = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    p_sh = param_shardings(params_struct, mesh, zero1=mcfg.fsdp or layout == "dp",
                           tp=layout == "tp")
    with mesh, axis_rules(mesh, rules):
        if shape.kind == "train":
            opt_struct = jax.eval_shape(
                lambda p: adamw_init(p, state_dtype=jnp.dtype(mcfg.opt_state_dtype)),
                params_struct)
            o_sh = opt_shardings(opt_struct, mesh, tp=layout == "tp")
            bspec = api.batch_specs(B, N)
            b_sh = batch_shardings(bspec, mesh, seq_parallel=seq_parallel,
                                   full_dp=layout == "dp")
            lowered = jax.jit(make_train_step(api), in_shardings=(p_sh, o_sh, b_sh),
                              donate_argnums=(0, 1)).lower(
                params_struct, opt_struct, bspec)
        elif shape.kind == "prefill":
            bspec = api.batch_specs(B, N)
            b_sh = batch_shardings(bspec, mesh, seq_parallel=seq_parallel,
                                   full_dp=layout == "dp")
            lowered = jax.jit(make_prefill_step(api), in_shardings=(p_sh, b_sh)).lower(
                params_struct, bspec)
        else:
            cspec = api.cache_specs(B, N)
            c_sh = cache_shardings(cspec, mesh, seq_parallel=seq_parallel)
            tok = jax.ShapeDtypeStruct((B,), jnp.int32)
            t_sh = batch_shardings(tok, mesh)
            lowered = jax.jit(make_serve_step(api), in_shardings=(p_sh, c_sh, t_sh),
                              donate_argnums=(1,)).lower(params_struct, cspec, tok)
    return lowered, mesh


def measure(lowered, mesh) -> dict:
    compiled = lowered.compile()
    hh = analyze_hlo(compiled.as_text())
    ma = compiled.memory_analysis()
    comp = hh["dot_flops_weighted"] / PEAK_FLOPS_BF16
    mem = hh["traffic_bytes_weighted"] / HBM_BW
    coll = hh["collective_wire_bytes"] / ICI_BW_PER_LINK
    peak = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    upcast = min(hh["bf16_upcast_bytes"], ma.temp_size_in_bytes)
    return {
        "compute_s": comp, "memory_s": mem, "collective_s": coll,
        "dominant": max(("compute", comp), ("memory", mem),
                        ("collective", coll), key=lambda t: t[1])[0],
        "bound_s": max(comp, mem, coll),
        "roofline_fraction": comp / max(comp, mem, coll),
        "peak_tpu_gib": max(peak - upcast,
                            ma.argument_size_in_bytes) / 2**30,
        "collectives": {k: round(v["bytes"] / 2**20)
                        for k, v in hh["collectives"].items()},
    }


def apply_overrides(mcfg, args):
    bsa = mcfg.bsa
    kw = {}
    if args.chunk is not None:
        kw["jnp_chunk_tokens"] = args.chunk
    if args.topk:
        kw["top_k"] = args.topk
    if args.ell:
        kw["cmp_block"] = args.ell
        kw["slc_block"] = args.ell
    if args.window:
        kw["local_window"] = args.window
    if args.backend:
        kw["backend"] = args.backend
    if kw:
        bsa = dataclasses.replace(bsa, **kw)
    m = {}
    if args.attn_seq:
        m["attn_shard_mode"] = "sequence"
    if args.fsdp:
        m["fsdp"] = True
    return mcfg.scaled(bsa=bsa, **m)


def time_kernel_train_step(args) -> None:
    """§Kernel-path training: EXECUTE (not just lower) one full fwd+bwd
    train step of BSA attention on a named backend (default ``pallas``;
    ``--backend jnp|interpret|...`` swaps it with no other changes) and
    report wall time — the measurement the differentiable Pallas path
    unlocks.  On this CPU container the pallas backend runs under interpret
    mode (set REPRO_PALLAS_INTERPRET=0 on TPU hosts for compiled numbers).

    Also reports PEAK step memory (argument + temp + output − aliased, from
    the compiled step's memory analysis) — the number the kernel-native GQA
    path moves, since the rep× ``repeat_kv`` K/V blowup is gone.

    With ``--batch B > 1`` the same step is ALSO timed as B sequential
    single-sample calls (the pre-ragged-batching trainer pattern) and both
    are reported as points/sec — the batched-path speedup measurement.
    ``--ragged`` builds a HIGH-VARIANCE mixed-size batch (sizes spanning N
    down to max(N//8, ball)) and times it BOTH ways: bucket-padded dummy
    slots (per-sample masks, the classic layout) and packed-varlen (one
    concatenated axis + offsets, ``bsa_attention_varlen`` — docs/varlen.md).
    The packed numbers are the headline record; the padded ones ride along
    so the padding-waste delta is visible in the same JSON.

    ``--autotune`` enables the tile autotuner (``kernels/tuning.py``): cache
    misses are measured with timed kernel runs and persisted to the JSON
    cache ($REPRO_TUNING_CACHE; unset keeps it in memory only); a
    second run hits the cache and re-measures nothing.  ``--bench-json``
    writes the measured record; ``--baseline BENCH_perf_iter.json`` compares
    against a committed record and exits non-zero if throughput regressed
    more than ``--max-regression`` (CI gate).

      PYTHONPATH=src python -m benchmarks.perf_iter --kernel-step \
          --n 256 --batch 8 --heads 4 --kv-heads 2 --head-dim 32 --ragged
    """
    import jax
    import jax.numpy as jnp

    from benchmarks.common import emit, time_fn
    from repro.core import BSAConfig, bsa_attention, bsa_init
    from repro.core.backend import resolve_backend_name
    from repro.kernels.common import should_interpret

    B, N, Hq, Hkv, D = args.batch, args.n, args.heads, args.kv_heads, args.head_dim
    ball = min(64, N)
    if N % ball or N % 8:
        raise SystemExit(f"--n {N} must be a multiple of the ball size {ball} "
                         "(and of the group size 8)")
    backend = args.backend or "pallas"
    cfg = BSAConfig(ball_size=ball, local_window=ball,
                    cmp_block=args.ell or 8, slc_block=args.ell or 8,
                    top_k=args.topk or 4, group_size=8, backend=backend,
                    score_dtype=args.score_dtype)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, N, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, N, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, N, Hkv, D), jnp.float32)
    if args.ragged:
        # HIGH-VARIANCE mixed-size batch: sizes span N down to max(N//8,
        # ball) — the regime where dummy-padded slots waste the most FLOPs
        # and the packed-varlen layout pays off hardest (docs/varlen.md)
        lo = max(N // 8, ball)
        lens = [max(lo, N - i * (N - lo) // max(B - 1, 1)) for i in range(B)]
        mask = jnp.stack([jnp.arange(N) < n for n in lens])
        n_pts = sum(lens)
    else:
        mask = None
        n_pts = B * N
    params = bsa_init(ks[3], cfg, n_heads=Hq, n_kv_heads=Hkv, head_dim=D,
                      d_model=Hq * D)

    def loss(p, q, k, v, m):
        return jnp.sum(bsa_attention(p, q, k, v, cfg=cfg, mask=m) ** 2)

    step = jax.jit(jax.value_and_grad(loss))

    def occupancy_report(fn, label):
        """One EAGER forward under the occupancy recorder (kernels/occupancy
        .py); recording is a no-op under jit tracing, so this is the only
        place live/total tile counts are concrete.  Returns {kernel:
        {live, total}} for the JSON record (None on non-kernel backends)."""
        from repro.kernels import occupancy as occ_mod
        with occ_mod.record_occupancy() as counts:
            jax.block_until_ready(fn())
        if not counts:
            print(f"# occupancy[{label}]: no kernel launches recorded "
                  f"(backend={backend})", flush=True)
            return None
        for kname, c in sorted(counts.items()):
            pct = 100.0 * c["live"] / max(c["total"], 1)
            print(f"# occupancy[{label}/{kname}]: {c['live']}/{c['total']} "
                  f"tiles live ({pct:.0f}%)", flush=True)
        return {kname: dict(c) for kname, c in counts.items()}

    occ = None
    if args.occupancy:
        occ = occupancy_report(
            lambda: bsa_attention(params, q, k, v, cfg=cfg, mask=mask),
            "padded" if args.ragged else "dense")

    def run(p, q, k, v, m):
        out, grads = step(p, q, k, v, m)
        return out

    us = time_fn(run, params, q, k, v, mask, warmup=2, iters=5)
    try:
        ma = step.lower(params, q, k, v, mask).compile().memory_analysis()
        peak_bytes = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                      + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        peak_bytes = None
    resolved = resolve_backend_name(backend)     # env/context may override
    if resolved in ("jnp", "interpret"):
        mode = resolved
    else:
        mode = f"{resolved}-{'interpret' if should_interpret() else 'compiled'}"
    pps = n_pts / (us / 1e6)
    tag = "_ragged" if args.ragged else ""      # distinct trajectory entries
    emit(f"perf_iter/kernel_train_step_b{B}_n{N}{tag}", us,
         f"mode={mode};heads={Hq}/{Hkv};d={D};points_per_sec={pps:.0f};"
         f"peak_bytes={peak_bytes};score_dtype={args.score_dtype}")

    packed_stats = None
    if args.ragged:
        # the same mixed batch on the PACKED-VARLEN layout: per-sample
        # ball-padded slices concatenated on one axis, offsets instead of
        # dummy batch slots (core.bsa.bsa_attention_varlen)
        from repro.core import bsa_attention_varlen
        padded_lens = [-(-n_i // ball) * ball for n_i in lens]
        total = sum(padded_lens)
        offs_list = [0]
        for pl in padded_lens:
            offs_list.append(offs_list[-1] + pl)
        offs = jnp.asarray(offs_list, jnp.int32)
        qp = jnp.concatenate([q[i, :padded_lens[i]] for i in range(B)], axis=0)
        kp = jnp.concatenate([k[i, :padded_lens[i]] for i in range(B)], axis=0)
        vp = jnp.concatenate([v[i, :padded_lens[i]] for i in range(B)], axis=0)
        maskp = jnp.concatenate(
            [jnp.arange(padded_lens[i]) < lens[i] for i in range(B)])

        def loss_pk(p, q, k, v, m):
            return jnp.sum(bsa_attention_varlen(p, q, k, v, cfg=cfg,
                                                offsets=offs, mask=m) ** 2)

        step_pk = jax.jit(jax.value_and_grad(loss_pk))
        occ_pk = None
        if args.occupancy:
            occ_pk = occupancy_report(
                lambda: bsa_attention_varlen(params, qp, kp, vp, cfg=cfg,
                                             offsets=offs, mask=maskp),
                "packed")

        def run_pk(p, q, k, v, m):
            out, grads = step_pk(p, q, k, v, m)
            return out

        us_pk = time_fn(run_pk, params, qp, kp, vp, maskp, warmup=2, iters=5)
        try:
            ma = step_pk.lower(params, qp, kp, vp, maskp).compile() \
                        .memory_analysis()
            peak_pk = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                       + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        except Exception:
            peak_pk = None
        pps_pk = n_pts / (us_pk / 1e6)
        emit(f"perf_iter/kernel_train_step_b{B}_n{N}_packed", us_pk,
             f"mode={mode};points_per_sec={pps_pk:.0f};peak_bytes={peak_pk};"
             f"rows={total}vs{B * N}")
        print(f"# packed-varlen vs bucket-padded: {us / us_pk:.2f}x "
              f"points/sec ({pps_pk:.0f} vs {pps:.0f}); "
              f"{total} packed rows vs {B * N} padded", flush=True)
        packed_stats = {"us_per_step": round(us_pk, 1),
                        "points_per_sec": round(pps_pk, 1),
                        "peak_bytes": peak_pk,
                        "packed_rows": total, "padded_rows": B * N}
        if occ_pk is not None:
            packed_stats["occupancy"] = occ_pk

    record = {
        "shape": {"batch": B, "n": N, "heads": Hq, "kv_heads": Hkv,
                  "head_dim": D, "ragged": bool(args.ragged)},
        "mode": mode, "backend": resolved, "autotune": bool(args.autotune),
        "score_dtype": args.score_dtype,
        "us_per_step": round(us, 1), "points_per_sec": round(pps, 1),
        "peak_bytes": peak_bytes,
    }
    if occ is not None:
        record["occupancy"] = occ
    if packed_stats is not None:
        # headline = packed (what the gate tracks); padded rides along
        record["padded"] = {"us_per_step": round(us, 1),
                            "points_per_sec": round(pps, 1),
                            "peak_bytes": peak_bytes}
        record["packed"] = packed_stats
        record.update(us_per_step=packed_stats["us_per_step"],
                      points_per_sec=packed_stats["points_per_sec"],
                      peak_bytes=packed_stats["peak_bytes"])
    if args.bench_json:
        Path(args.bench_json).write_text(json.dumps(record, indent=1) + "\n")
        print(f"# wrote {args.bench_json}", flush=True)
    if args.baseline:
        _check_regression(record, args.baseline, args.max_regression)

    if B > 1:
        # baseline: the SAME work as B sequential single-sample steps — the
        # pre-ragged-batching trainer pattern.  A per-sample loop must also
        # sum the per-sample losses and ACCUMULATE gradients across samples
        # (the batched step gets both for free from one backward).
        qs = [q[i:i + 1] for i in range(B)]
        ks_ = [k[i:i + 1] for i in range(B)]
        vs = [v[i:i + 1] for i in range(B)]
        ms = [mask[i:i + 1] if mask is not None else None for i in range(B)]

        def run_seq(p):
            total, acc = None, None
            for i in range(B):
                li, gi = step(p, qs[i], ks_[i], vs[i], ms[i])
                total = li if total is None else total + li
                acc = gi if acc is None else jax.tree.map(jnp.add, acc, gi)
            return total, acc

        us_seq = time_fn(run_seq, params, warmup=2, iters=5)
        pps_seq = n_pts / (us_seq / 1e6)
        emit(f"perf_iter/kernel_train_step_seq{B}_n{N}{tag}", us_seq,
             f"mode={mode};points_per_sec={pps_seq:.0f}")
        print(f"# batched step vs {B} sequential steps: "
              f"{us_seq / us:.2f}x points/sec "
              f"({pps:.0f} vs {pps_seq:.0f})", flush=True)


def time_serve_benchmark(args) -> None:
    """§Serving throughput: lockstep batches vs continuous batching over the
    paged KV cache, on the SAME ragged request mix (half short, half long
    prompts — the regime where a rectangular batch wastes the most steps).

    Lockstep is the pre-paged engine: requests are grouped into rectangles
    of ``--slots``, each padded to its batch-max prompt length, and a batch
    only finishes when every slot has its ``--tokens`` generations.
    Continuous batching (``ServingEngine(paged=True).serve``) retires slots
    independently and admits queued requests mid-flight, so useful
    tokens/sec is the honest comparison: the SAME R·tokens generations
    divided by each mode's wall time.  Smoke-scale model on CPU — compare
    runs on similar hosts only.

      PYTHONPATH=src python -m benchmarks.perf_iter --serve \
          --slots 4 --requests 8 --tokens 16 --max-len 256
    """
    import time as _time

    import numpy as np

    from repro.configs import get_config
    from repro.configs.reduce import smoke_config
    from repro.models.api import model_api
    from repro.serving import ServingEngine

    mcfg = smoke_config(get_config(args.arch or "tinyllama-1.1b"))
    if args.backend:
        mcfg = mcfg.scaled(bsa=dataclasses.replace(mcfg.bsa,
                                                   backend=args.backend))
    api = model_api(mcfg)
    params = api.init(jax.random.PRNGKey(0))
    B, R, NEW, S = args.slots, args.requests, args.tokens, args.max_len
    rng = np.random.default_rng(0)
    lens = np.where(np.arange(R) % 2 == 0,
                    rng.integers(16, 33, R),
                    rng.integers(S // 2, S - NEW, R))
    prompts = [rng.integers(0, mcfg.vocab_size, int(n), dtype=np.int32)
               for n in lens]
    useful = R * NEW

    def run_lockstep(eng):
        for s in range(0, R, B):
            chunk = prompts[s:s + B]
            chunk = chunk + [chunk[-1]] * (B - len(chunk))   # dummy tail slots
            rect = np.zeros((B, max(len(p) for p in chunk)), np.int32)
            for i, p in enumerate(chunk):
                rect[i, :len(p)] = p       # zero-padded: cost model only —
            eng.reset()                    # lockstep CAN'T serve ragged rows
            eng.generate(rect, NEW)

    lock = ServingEngine(api, params, batch_slots=B, max_len=S)
    run_lockstep(lock)                                       # jit warmup
    t0 = _time.perf_counter()
    run_lockstep(lock)
    t_lock = _time.perf_counter() - t0

    paged = ServingEngine(api, params, batch_slots=B, max_len=S, paged=True)
    paged.serve(prompts, max_new_tokens=NEW)                 # jit warmup
    paged.reset()
    t0 = _time.perf_counter()
    paged.serve(prompts, max_new_tokens=NEW)
    t_paged = _time.perf_counter() - t0
    steps_paged = paged.serve_steps // 2                     # two equal runs

    tps_lock = useful / t_lock
    tps_paged = useful / t_paged
    from benchmarks.common import emit
    emit(f"perf_iter/serve_lockstep_b{B}_r{R}", t_lock * 1e6 / useful,
         f"tokens_per_sec={tps_lock:.1f}")
    emit(f"perf_iter/serve_paged_b{B}_r{R}", t_paged * 1e6 / useful,
         f"tokens_per_sec={tps_paged:.1f};steps={steps_paged};"
         f"page={paged.page}")
    print(f"# continuous vs lockstep: {tps_paged / tps_lock:.2f}x useful "
          f"tokens/sec ({tps_paged:.0f} vs {tps_lock:.0f}) on "
          f"{R} requests, prompt lens {lens.min()}..{lens.max()}", flush=True)

    record = {
        "serving": True,
        "shape": {"slots": B, "requests": R, "new_tokens": NEW, "max_len": S,
                  "prompt_lens": [int(n) for n in lens]},
        "page": paged.page,
        "lockstep": {"tokens_per_sec": round(tps_lock, 1),
                     "wall_s": round(t_lock, 3)},
        "paged": {"tokens_per_sec": round(tps_paged, 1),
                  "wall_s": round(t_paged, 3), "steps": steps_paged},
        "tokens_per_sec": round(tps_paged, 1),
        "speedup_vs_lockstep": round(tps_paged / tps_lock, 2),
    }
    if args.bench_json:
        Path(args.bench_json).write_text(json.dumps(record, indent=1) + "\n")
        print(f"# wrote {args.bench_json}", flush=True)
    if args.baseline:
        _check_regression(record, args.baseline, args.max_regression)


def time_mesh_benchmark(args) -> None:
    """§Sharded scaling: one executed fwd+bwd BSA train step on a single
    device vs the SAME step under the ``"sharded"`` backend on an N-device
    ``make_local_mesh`` (``--mesh N`` — devices are XLA host-platform fakes
    on CPU, so this measures the shard_map partitioning overhead/benefit,
    not real multi-chip speedup; compare runs on similar hosts only).

    The recorded ``scaling_efficiency`` is the sharded/single throughput
    RATIO measured in the same invocation, so the CI gate is invariant to
    runner speed (the serving ``speedup_vs_lockstep`` pattern).  On shared-
    core fake devices the honest expectation is ≈1, not N.

      PYTHONPATH=src python -m benchmarks.perf_iter --mesh 8 \
          --n 1024 --batch 2 --heads 4 --kv-heads 2 --head-dim 32
    """
    import jax
    import jax.numpy as jnp

    from benchmarks.common import emit, time_fn
    from repro.core import BSAConfig, bsa_attention, bsa_init
    from repro.core.backend import use_backend
    from repro.distributed import mesh_context
    from repro.launch.mesh import make_local_mesh

    p = args.mesh
    B, N = args.batch, args.n
    Hq, Hkv, D = args.heads, args.kv_heads, args.head_dim
    ball = 64
    if N % (p * ball):
        raise SystemExit(f"--mesh {p}: --n {N} must be a multiple of "
                         f"{p} devices x ball {ball}")
    cfg = BSAConfig(ball_size=ball, local_window=ball, cmp_block=8, top_k=4,
                    group_size=8, backend=args.backend or "jnp")
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    params = bsa_init(ks[0], cfg, n_heads=Hq, n_kv_heads=Hkv, head_dim=D,
                      d_model=Hq * D)
    q = jax.random.normal(ks[1], (B, N, Hq, D), jnp.float32)
    k = jax.random.normal(ks[2], (B, N, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[3], (B, N, Hkv, D), jnp.float32)

    def loss(p_, q, k, v):
        return (bsa_attention(p_, q, k, v, cfg=cfg) ** 2).sum() / N

    n_pts = B * N
    step_1 = jax.jit(jax.value_and_grad(loss))        # traced single-device
    us_1 = time_fn(lambda *a: jax.block_until_ready(step_1(*a)),
                   params, q, k, v, warmup=2, iters=5)
    mesh = make_local_mesh(p)
    with mesh_context(mesh), use_backend("sharded"):
        step_p = jax.jit(jax.value_and_grad(loss))    # traced sharded
        us_p = time_fn(lambda *a: jax.block_until_ready(step_p(*a)),
                       params, q, k, v, warmup=2, iters=5)
    pps_1, pps_p = n_pts / (us_1 / 1e6), n_pts / (us_p / 1e6)
    eff = pps_p / pps_1
    emit(f"perf_iter/mesh{p}_train_step_b{B}_n{N}", us_p,
         f"points_per_sec={pps_p:.0f};single_dev={pps_1:.0f};"
         f"scaling_efficiency={eff:.2f}")
    print(f"# sharded x{p} vs single device: {eff:.2f}x points/sec "
          f"({pps_p:.0f} vs {pps_1:.0f})", flush=True)

    record = {
        "mesh": p,
        "shape": {"batch": B, "n": N, "heads": Hq, "kv_heads": Hkv,
                  "head_dim": D},
        "backend_inner": args.backend or "jnp",
        "single": {"us_per_step": round(us_1, 1),
                   "points_per_sec": round(pps_1, 1)},
        "sharded": {"us_per_step": round(us_p, 1),
                    "points_per_sec": round(pps_p, 1)},
        "points_per_sec": round(pps_p, 1),
        "scaling_efficiency": round(eff, 3),
    }
    if args.ring:
        record["ring"] = _time_ring_leg(args, mesh, p, B, N, Hq, Hkv, D)
    if args.bench_json:
        Path(args.bench_json).write_text(json.dumps(record, indent=1) + "\n")
        print(f"# wrote {args.bench_json}", flush=True)
    if args.baseline:
        _check_regression(record, args.baseline, args.max_regression)


def _time_ring_leg(args, mesh, p, B, N, Hq, Hkv, D) -> dict:
    """§Ring context parallelism: one executed fwd+bwd NSA-causal step —
    token-causal ring flash + ring selection, the two ops that used to fall
    back — single device vs sharded, in the same invocation.  Alongside the
    runner-speed-invariant scaling ratio the record stamps the ANALYTIC
    invariants the ring buys: per-shard selection K/V bytes (1/p of the old
    replicated strategy), the causal hop skip rate from the static
    ``ring_hop_live`` table (~half of p² shard-hops), and the v5e ICI
    roofline of one rotation cycle."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import emit, time_fn
    from repro.core import BSAConfig
    from repro.core.backend import use_backend
    from repro.core.nsa_causal import nsa_causal_attention, nsa_init
    from repro.distributed import mesh_context
    from repro.kernels.occupancy import ring_hop_live
    from repro.launch.mesh import ring_roofline_us

    cfg = BSAConfig(ball_size=min(64, N), local_window=min(64, N),
                    cmp_block=8, slc_block=8, top_k=4, group_size=8,
                    backend=args.backend or "jnp")
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    params = nsa_init(ks[0], cfg, n_heads=Hq, n_kv_heads=Hkv, head_dim=D,
                      d_model=Hq * D)
    q = jax.random.normal(ks[1], (B, N, Hq, D), jnp.float32)
    k = jax.random.normal(ks[2], (B, N, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[3], (B, N, Hkv, D), jnp.float32)

    def loss(p_, q, k, v):
        return (nsa_causal_attention(p_, q, k, v, cfg=cfg) ** 2).sum() / N

    step_1 = jax.jit(jax.value_and_grad(loss))
    us_1 = time_fn(lambda *a: jax.block_until_ready(step_1(*a)),
                   params, q, k, v, warmup=2, iters=5)
    with mesh_context(mesh), use_backend("sharded"):
        step_p = jax.jit(jax.value_and_grad(loss))
        us_p = time_fn(lambda *a: jax.block_until_ready(step_p(*a)),
                       params, q, k, v, warmup=2, iters=5)
    n_pts = B * N
    pps_1, pps_p = n_pts / (us_1 / 1e6), n_pts / (us_p / 1e6)
    eff = pps_p / pps_1

    live = ring_hop_live(p, N // p, causal=True)
    hops_live, hops_total = int(live.sum()), p * p
    # per-shard selection K/V residency: the old strategy all-gathered the
    # full fp32 K+V; the ring keeps the local slab and rotates it
    repl_bytes = 2 * B * N * Hkv * D * 4
    ring_bytes = repl_bytes // p
    emit(f"perf_iter/ring{p}_nsa_step_b{B}_n{N}", us_p,
         f"points_per_sec={pps_p:.0f};single_dev={pps_1:.0f};"
         f"scaling_efficiency={eff:.2f};hops={hops_live}/{hops_total};"
         f"kv_bytes_per_shard={ring_bytes}")
    print(f"# ring x{p} vs single device: {eff:.2f}x points/sec "
          f"({pps_p:.0f} vs {pps_1:.0f}); causal hops {hops_live}/{hops_total}"
          f" ({100 * hops_live // hops_total}%); selection K/V/shard "
          f"{ring_bytes} vs {repl_bytes} replicated (1/{p})", flush=True)
    return {
        "single": {"us_per_step": round(us_1, 1),
                   "points_per_sec": round(pps_1, 1)},
        "sharded": {"us_per_step": round(us_p, 1),
                    "points_per_sec": round(pps_p, 1)},
        "scaling_efficiency": round(eff, 3),
        "causal_hops": {"live": hops_live, "total": hops_total,
                        "skip_pct": round(100 * (1 - hops_live / hops_total))},
        "selection_kv_bytes_per_shard": {"ring": ring_bytes,
                                         "replicated": repl_bytes,
                                         "ratio": round(ring_bytes / repl_bytes, 4)},
        "rotation_roofline_us_v5e": round(
            ring_roofline_us(ring_bytes, p - 1), 2),
    }


def _check_regression(record: dict, baseline_path: str, max_regression: float):
    """CI gate: fail when throughput regressed > max_regression vs the
    committed baseline record.  Ragged records compare against the
    baseline's ``ragged_varlen.packed`` entry, bf16 ones against
    ``mixed_precision.after`` (fp32 and bf16 wall times are not comparable
    on CPU, which emulates bf16); dense fp32 records read the ``after``
    entry (or a flat record)."""
    p = Path(baseline_path)
    if not p.exists():
        print(f"# baseline {baseline_path} missing — regression gate skipped",
              flush=True)
        return
    base = json.loads(p.read_text())
    if record.get("mesh"):
        # gate on the sharded/single-device RATIO measured in one
        # invocation — invariant to runner speed like the serving gate
        base_eff = base.get("sharded_mesh", {}).get("scaling_efficiency")
        if not base_eff:
            print("# baseline has no sharded_mesh.scaling_efficiency — "
                  "regression gate skipped", flush=True)
            return
        eff = record["scaling_efficiency"]
        ratio = eff / base_eff
        print(f"# scaling efficiency vs baseline: {ratio:.2f}x "
              f"({eff:.2f} vs {base_eff:.2f} sharded/single)", flush=True)
        if ratio < 1.0 - max_regression:
            raise SystemExit(
                f"sharded scaling regression: {eff:.2f} sharded/single is "
                f"{(1 - ratio) * 100:.0f}% below baseline {base_eff:.2f} "
                f"(allowed: {max_regression * 100:.0f}%)")
        ring_eff = record.get("ring", {}).get("scaling_efficiency")
        base_ring = base.get("sharded_ring", {}).get("scaling_efficiency")
        if ring_eff and base_ring:
            ratio = ring_eff / base_ring
            print(f"# ring scaling efficiency vs baseline: {ratio:.2f}x "
                  f"({ring_eff:.2f} vs {base_ring:.2f} sharded/single)",
                  flush=True)
            if ratio < 1.0 - max_regression:
                raise SystemExit(
                    f"ring scaling regression: {ring_eff:.2f} sharded/single "
                    f"is {(1 - ratio) * 100:.0f}% below baseline "
                    f"{base_ring:.2f} (allowed: {max_regression * 100:.0f}%)")
        elif ring_eff:
            print("# baseline has no sharded_ring.scaling_efficiency — "
                  "ring gate skipped", flush=True)
        return
    if record.get("serving"):
        # gate on the paged/lockstep RATIO, not absolute tok/s: both modes
        # run on the same host in the same invocation, so the ratio is
        # invariant to runner speed while absolute wall-clock is not
        base_spd = base.get("serving_paged", {}).get("after", {}) \
                       .get("speedup_vs_lockstep")
        if not base_spd:
            print("# baseline has no serving_paged.after.speedup_vs_lockstep"
                  " — regression gate skipped", flush=True)
            return
        spd = record["speedup_vs_lockstep"]
        ratio = spd / base_spd
        print(f"# serving speedup vs baseline: {ratio:.2f}x "
              f"({spd:.2f}x vs {base_spd:.2f}x over lockstep)", flush=True)
        if ratio < 1.0 - max_regression:
            raise SystemExit(
                f"serving throughput regression: {spd:.2f}x over lockstep is "
                f"{(1 - ratio) * 100:.0f}% below baseline {base_spd:.2f}x "
                f"(allowed: {max_regression * 100:.0f}%)")
        return
    if record["shape"].get("ragged") and "ragged_varlen" in base:
        base = base["ragged_varlen"].get("packed", {})
    elif (record.get("score_dtype") == "bfloat16"
          and "mixed_precision" in base):
        base = base["mixed_precision"].get("after", {})
    else:
        base = base.get("after", base)           # before/after trajectory file
    base_pps = base.get("points_per_sec")
    if not base_pps:
        print("# baseline has no points_per_sec — regression gate skipped",
              flush=True)
        return
    ratio = record["points_per_sec"] / base_pps
    print(f"# throughput vs baseline: {ratio:.2f}x "
          f"({record['points_per_sec']:.0f} vs {base_pps:.0f} points/sec)",
          flush=True)
    if ratio < 1.0 - max_regression:
        raise SystemExit(
            f"throughput regression: {record['points_per_sec']:.0f} points/sec "
            f"is {(1 - ratio) * 100:.0f}% below baseline {base_pps:.0f} "
            f"(allowed: {max_regression * 100:.0f}%)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--layout", default="tp", choices=["tp", "dp"])
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--topk", type=int, default=0)
    ap.add_argument("--ell", type=int, default=0)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--attn-seq", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--backend", default=None,
                    help="attention backend: jnp | pallas | interpret | auto "
                         "| any registered plug-in (kernel-step default: pallas)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--kernel-step", action="store_true",
                    help="time one executed fwd+bwd BSA step on the kernel path "
                         "(--batch B>1 also times B sequential single-sample "
                         "steps for the batched-path comparison)")
    ap.add_argument("--ragged", action="store_true",
                    help="kernel-step: high-variance mixed-size batch, timed "
                         "both bucket-padded and packed-varlen (offsets)")
    ap.add_argument("--occupancy", action="store_true",
                    help="kernel-step: run one eager forward under the tile-"
                         "occupancy recorder and report live/total tile "
                         "counts per kernel (kernels/occupancy.py); counts "
                         "are included in the --bench-json record")
    ap.add_argument("--score-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="kernel-step: BSAConfig.score_dtype — bfloat16 runs "
                         "the kernel precision contract (bf16 QK^T/PV "
                         "operands, fp32 accumulation)")
    ap.add_argument("--autotune", action="store_true",
                    help="enable the tile autotuner (kernels/tuning.py): "
                         "measure candidate (tq, tk) grids on cache miss and "
                         "persist to $REPRO_TUNING_CACHE "
                         "(unset: in memory only); second run hits cache")
    ap.add_argument("--bench-json", default=None,
                    help="kernel-step: write the measured record "
                         "(points/sec, peak bytes) to this JSON file")
    ap.add_argument("--baseline", default=None,
                    help="kernel-step: committed baseline JSON to gate "
                         "against (BENCH_perf_iter.json)")
    ap.add_argument("--max-regression", type=float, default=0.2,
                    help="allowed fractional throughput drop vs --baseline "
                         "before failing (default 0.2)")
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=32)
    ap.add_argument("--mesh", type=int, default=0,
                    help="time one fwd+bwd BSA step single-device vs the "
                         "'sharded' backend on an N-device local mesh; "
                         "--bench-json/--baseline gate the runner-speed-"
                         "invariant scaling_efficiency ratio")
    ap.add_argument("--ring", action="store_true",
                    help="with --mesh: also time an NSA-causal step (token-"
                         "causal ring flash + ring selection) and record the "
                         "sharded_ring entry — scaling efficiency, causal "
                         "hop skip rate, per-shard selection K/V bytes")
    ap.add_argument("--serve", action="store_true",
                    help="time lockstep batches vs paged continuous batching "
                         "on a ragged request mix (useful tokens/sec; "
                         "--bench-json/--baseline gate the paged number)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    args = ap.parse_args()

    if args.autotune:
        # must be set before the first attention trace resolves tiles
        os.environ["REPRO_AUTOTUNE"] = "1"
    if args.serve:
        time_serve_benchmark(args)
        return
    if args.mesh:
        time_mesh_benchmark(args)
        return
    if args.kernel_step:
        time_kernel_train_step(args)
        return
    if not args.arch or not args.shape:
        ap.error("--arch and --shape are required (unless --kernel-step)")

    mcfg = apply_overrides(get_config(args.arch), args)
    lowered, mesh = lower_with_overrides(args.arch, args.shape, mcfg=mcfg,
                                         layout=args.layout)
    m = measure(lowered, mesh)
    base_p = Path(f"results/dryrun/{args.arch}__{args.shape}__pod1.json")
    base = json.loads(base_p.read_text()) if base_p.exists() else None
    print(json.dumps({"tag": args.tag or "iter", **m}, indent=1))
    if base and base.get("ok"):
        b_comp = base["flops_per_device"] / PEAK_FLOPS_BF16
        b_mem = base["traffic_bytes_per_device"] / HBM_BW
        b_coll = base["collective_wire_bytes"] / ICI_BW_PER_LINK
        b_bound = max(b_comp, b_mem, b_coll)
        print(f"baseline bound {b_bound*1e3:.1f} ms → now {m['bound_s']*1e3:.1f} ms "
              f"({b_bound/max(m['bound_s'],1e-12):.2f}x better); "
              f"roofline frac {b_comp/max(b_bound,1e-12):.3f} → {m['roofline_fraction']:.3f}")


if __name__ == "__main__":
    main()
