"""Distribution tests.  Multi-device cases run in SUBPROCESSES so the main
pytest process keeps its single-device jax runtime (the device count is
frozen at first backend init)."""

import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import logical_to_spec
from repro.launch.mesh import make_mesh


def _run(src: str, n_dev: int = 8) -> str:
    """Run python source with n_dev fake devices; return stdout."""
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        env={"XLA_FLAGS": f"--xla_force_host_platform_device_count={n_dev}",
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=900, cwd=".")
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# sharding rules (single device, pure logic)
# ---------------------------------------------------------------------------

def test_logical_to_spec_divisibility_guard():
    mesh = make_mesh((1,), ("model",))

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    rules = {"heads": ("model",), "batch": ("data",), "d_model": None}
    # 56 heads not divisible by 16 → replicated; 64 heads → sharded
    spec = logical_to_spec(("batch", "seq", "heads"), (256, 4096, 56), FakeMesh, rules)
    assert spec == P("data", None, None)
    spec = logical_to_spec(("batch", "seq", "heads"), (256, 4096, 64), FakeMesh, rules)
    assert spec == P("data", None, "model")


def test_param_shardings_patterns():
    from repro.distributed.params import param_shardings
    mesh = make_mesh((1,), ("model",))

    class M:
        shape = {"model": 1}
        def __eq__(self, o): return True
    params = {
        "embed": {"table": jax.ShapeDtypeStruct((1024, 64), np.float32)},
        "layers": {"pos0": {"attn": {
            "wq": {"w": jax.ShapeDtypeStruct((4, 64, 128), np.float32)},
            "wo": {"w": jax.ShapeDtypeStruct((4, 128, 64), np.float32)}}}},
    }
    sh = param_shardings(params, mesh)
    # with model axis of size 1 everything is effectively replicated but the
    # tree structure must match exactly
    assert jax.tree.structure(sh) == jax.tree.structure(params)


# ---------------------------------------------------------------------------
# pipeline parallelism (4 fake devices)
# ---------------------------------------------------------------------------

def test_pipeline_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed.pipeline import pipeline_apply

        S, n_micro, B, d = 4, 8, 2, 16
        mesh = make_mesh((S,), ("stage",))
        key = jax.random.PRNGKey(0)
        Ws = jax.random.normal(key, (S, d, d)) * 0.3

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, B, d))
        with mesh:
            out = pipeline_apply(stage_fn, Ws, x, mesh=mesh)
        # sequential reference
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ Ws[s])
        err = float(jnp.abs(out - ref).max())
        print("PIPE_ERR", err)
        assert err < 1e-5, err
    """, n_dev=4)
    assert "PIPE_ERR" in out


# ---------------------------------------------------------------------------
# compressed cross-pod gradient reduction (2 fake devices = 2 pods)
# ---------------------------------------------------------------------------

def test_compressed_psum_close_to_exact():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim.compress import compressed_psum

        mesh = make_mesh((2,), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (2, 1024))

        def f(gs, err):
            total, resid = compressed_psum(gs[0], err[0], "pod")
            return total[None], resid[None]

        total, resid = shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                                 out_specs=(P("pod"), P("pod")),
                                 check_vma=False)(g, jnp.zeros_like(g))
        exact = g.sum(0)
        rel = float(jnp.abs(total[0] - exact).max() / (jnp.abs(exact).max()))
        print("REL", rel)
        assert rel < 0.02, rel                       # int8 quantization error
        # error feedback: residual carries exactly the quantization error
        assert float(jnp.abs(resid).max()) > 0
    """, n_dev=2)
    assert "REL" in out


# ---------------------------------------------------------------------------
# small-mesh dry-run smoke (8 fake devices): lowering machinery end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_small_mesh_dryrun_smoke():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.reduce import smoke_config
        from repro.models.api import model_api
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_train_step
        from repro.distributed.params import param_shardings, opt_shardings, batch_shardings
        from repro.distributed.sharding import axis_rules
        from repro.optim import adamw_init

        mcfg = smoke_config(get_config("tinyllama-1.1b"))
        api = model_api(mcfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        pstruct = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        ostruct = jax.eval_shape(lambda p: adamw_init(p), pstruct)
        p_sh = param_shardings(pstruct, mesh)
        o_sh = opt_shardings(ostruct, mesh)
        bspec = api.batch_specs(8, 256)
        b_sh = batch_shardings(bspec, mesh)
        with mesh, axis_rules(mesh):
            lowered = jax.jit(make_train_step(api),
                              in_shardings=(p_sh, o_sh, b_sh)).lower(
                pstruct, ostruct, bspec)
            compiled = lowered.compile()
        ma = compiled.memory_analysis()
        print("ARGS", ma.argument_size_in_bytes)
        assert ma.argument_size_in_bytes > 0
    """, n_dev=8)
    assert "ARGS" in out


@pytest.mark.slow
def test_small_mesh_execution_correctness():
    """Sharded training step must produce the SAME loss as single-device."""
    src_tpl = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.reduce import smoke_config
        from repro.models.api import model_api
        from repro.launch.steps import make_train_step
        from repro.optim import adamw_init
        {mesh_setup}
        mcfg = smoke_config(get_config("tinyllama-1.1b"))
        api = model_api(mcfg)
        params = api.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        rng = np.random.default_rng(0)
        batch = api.make_batch(rng, 4, 256)
        step = make_train_step(api)
        {run}
        print("LOSS %.6f" % float(metrics["loss"]))
    """)
    single = _run(src_tpl.format(
        mesh_setup="", run="params, opt, metrics = jax.jit(step)(params, opt, batch)"),
        n_dev=1)
    multi = _run(src_tpl.format(
        mesh_setup="""
from repro.launch.mesh import make_mesh
from repro.distributed.sharding import axis_rules
from repro.distributed.params import param_shardings, opt_shardings
mesh = make_mesh((2, 2), ("data", "model"))
""",
        run="""
p_sh = param_shardings(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params), mesh)
with mesh, axis_rules(mesh):
    params = jax.device_put(params, p_sh)
    params, opt, metrics = jax.jit(step)(params, opt, batch)
"""), n_dev=4)
    l1 = float(single.split("LOSS")[1])
    l2 = float(multi.split("LOSS")[1])
    assert abs(l1 - l2) < 5e-3, (l1, l2)


# ---------------------------------------------------------------------------
# mesh builders (satellite: CPU-friendly construction + clear errors)
# ---------------------------------------------------------------------------

def test_make_local_mesh_uses_existing_devices():
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh()
    assert mesh.shape["data"] == len(jax.devices())
    with pytest.raises(RuntimeError, match="device"):
        make_local_mesh(len(jax.devices()) + 1)


def test_make_production_mesh_clear_error_on_small_host():
    from repro.launch.mesh import make_production_mesh
    if len(jax.devices()) >= 256:
        pytest.skip("enough devices for a production mesh")
    with pytest.raises(RuntimeError, match="make_local_mesh"):
        make_production_mesh()


# ---------------------------------------------------------------------------
# logical_to_spec fallback paths (divisibility warning + used-axis)
# ---------------------------------------------------------------------------

def test_logical_to_spec_warns_once_on_divisibility_failure():
    import warnings

    class FakeMesh:
        shape = {"model": 12}
    rules = {"heads": ("model",)}
    with pytest.warns(RuntimeWarning, match="'heads'.*50.*model.*12"):
        spec = logical_to_spec(("heads",), (50,), FakeMesh, rules)
    assert spec == P(None)
    # one-shot: the same failing combo never warns again
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert logical_to_spec(("heads",), (50,), FakeMesh, rules) == P(None)


def test_logical_to_spec_used_axis_fallback_is_silent():
    import warnings

    class FakeMesh:
        shape = {"model": 4}
    rules = {"heads": ("model",), "d_ff": ("model",)}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        # d_ff loses the already-used model axis → structural replication,
        # no warning (nothing actionable about it)
        spec = logical_to_spec(("heads", "d_ff"), (8, 64), FakeMesh, rules)
    assert spec == P("model", None)


# ---------------------------------------------------------------------------
# "sharded" backend: registration, mesh requirement, 1-device passthrough
# ---------------------------------------------------------------------------

def _tiny_bsa_case(seed=0, N=128):
    import jax.numpy as jnp
    from repro.core import BSAConfig
    from repro.core.bsa import bsa_init
    cfg = BSAConfig(ball_size=32, local_window=32, cmp_block=8, top_k=2,
                    group_size=8, backend="jnp")
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = bsa_init(ks[0], cfg, n_heads=4, n_kv_heads=2, head_dim=8,
                      d_model=32)
    q = jax.random.normal(ks[1], (2, N, 4, 8), jnp.float32)
    k = jax.random.normal(ks[2], (2, N, 2, 8), jnp.float32)
    v = jax.random.normal(ks[3], (2, N, 2, 8), jnp.float32)
    return cfg, params, q, k, v


def test_sharded_backend_registered_via_registry():
    from repro.core.backend import get_backend
    bk = get_backend("sharded")
    assert bk.name == "sharded" and bk.requires_mesh


def test_sharded_backend_requires_mesh_context():
    from repro.core.backend import use_backend
    from repro.core.bsa import bsa_attention
    cfg, params, q, k, v = _tiny_bsa_case()
    with use_backend("sharded"):
        with pytest.raises(RuntimeError, match="mesh_context"):
            bsa_attention(params, q, k, v, cfg=cfg)


def test_sharded_single_device_mesh_passthrough():
    import jax.numpy as jnp
    from repro.core.backend import use_backend
    from repro.core.bsa import bsa_attention
    from repro.distributed import mesh_context
    from repro.launch.mesh import make_local_mesh
    cfg, params, q, k, v = _tiny_bsa_case()
    ref = bsa_attention(params, q, k, v, cfg=cfg)
    with mesh_context(make_local_mesh(1)), use_backend("sharded"):
        out = bsa_attention(params, q, k, v, cfg=cfg)
    assert float(jnp.abs(ref - out).max()) < 1e-6


def test_engines_fail_fast_without_mesh():
    from repro.serving.engine import GeometryEngine, ServingEngine

    class _API:      # the fail-fast fires before anything else is touched
        class mcfg:
            class bsa:
                backend = None
    with pytest.raises(ValueError, match="mesh_context"):
        ServingEngine(_API, None, batch_slots=1, max_len=64,
                      backend="sharded")
    with pytest.raises(ValueError, match="mesh_context"):
        GeometryEngine(_API, None, backend="sharded")


# ---------------------------------------------------------------------------
# sharded == single-device parity (8 fake devices, subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_backend_parity_8dev():
    """fwd + full grads vs the unsharded jnp oracle (atol 1e-5 fp32) for
    bsa_attention (dense + ragged) and nsa_causal_attention, the packed-
    varlen fallback seam, and the indivisible-shape fallback warning."""
    out = _run("""
        import warnings
        import jax, jax.numpy as jnp
        from repro.core import BSAConfig
        from repro.core.bsa import bsa_attention, bsa_attention_varlen, bsa_init
        from repro.core.nsa_causal import nsa_causal_attention, nsa_init
        from repro.core.backend import use_backend
        from repro.distributed import mesh_context
        from repro.launch.mesh import make_local_mesh

        B, N, Hq, Hkv, D = 2, 512, 4, 2, 16
        cfg = BSAConfig(ball_size=64, local_window=64, cmp_block=8, top_k=4,
                        group_size=8, backend="jnp")
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        bparams = bsa_init(ks[0], cfg, n_heads=Hq, n_kv_heads=Hkv,
                           head_dim=D, d_model=Hq * D)
        nparams = nsa_init(ks[4], cfg, n_heads=Hq, n_kv_heads=Hkv,
                           head_dim=D, d_model=Hq * D)
        q = jax.random.normal(ks[1], (B, N, Hq, D), jnp.float32)
        k = jax.random.normal(ks[2], (B, N, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[3], (B, N, Hkv, D), jnp.float32)
        # ragged batch: row 1 real only up to 320 of 512
        mask = jnp.arange(N)[None, :] < jnp.array([N, 320])[:, None]
        mesh = make_local_mesh()
        assert mesh.shape["data"] == 8

        def tree_err(a, b):
            return max(jax.tree.leaves(jax.tree.map(
                lambda x, y: float(jnp.abs(x - y).max()), a, b)))

        with warnings.catch_warnings(record=True) as wrec:
            warnings.simplefilter("always")
            for name, fn, p in [("bsa", bsa_attention, bparams),
                                ("nsa", nsa_causal_attention, nparams)]:
                for m in (None, mask):
                    def loss(p, q, k, v):
                        o = fn(p, q, k, v, cfg=cfg, mask=m)
                        return (o ** 2).sum() / N   # O(1) grads: atol is
                                                     # a ~1e-5 RELATIVE bar
                    ref_o = fn(p, q, k, v, cfg=cfg, mask=m)
                    ref_g = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(p, q, k, v)
                    with mesh_context(mesh), use_backend("sharded"):
                        sh_o = jax.jit(lambda p, q, k, v: fn(
                            p, q, k, v, cfg=cfg, mask=m))(p, q, k, v)
                        sh_g = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(p, q, k, v)
                    eo, eg = tree_err(ref_o, sh_o), tree_err(ref_g, sh_g)
                    tag = "dense" if m is None else "ragged"
                    print(name, tag, "fwd", eo, "grad", eg)
                    assert eo < 1e-5 and eg < 1e-5, (name, tag, eo, eg)
        # every op (incl. token-causal flash + selection, once fallbacks)
        # must now shard on divisible shapes — zero falls-back warnings
        assert not any("falls back" in str(x.message) for x in wrec), \\
            [str(x.message) for x in wrec]

        # packed-varlen seam: now SEGMENT-SHARDED (LPT re-layout), not a
        # fallback — parity must hold with no falls-back warning at all
        offs = jnp.array([0, 256, 448, 512], jnp.int32)
        qp, kp, vp = q[0], k[0], v[0]
        ref_vl = bsa_attention_varlen(bparams, qp, kp, vp, cfg=cfg, offsets=offs)
        with mesh_context(mesh), use_backend("sharded"):
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                sh_vl = bsa_attention_varlen(bparams, qp, kp, vp, cfg=cfg,
                                             offsets=offs)
            assert not any("falls back" in str(x.message) for x in w), \\
                [str(x.message) for x in w]
        assert float(jnp.abs(ref_vl - sh_vl).max()) < 1e-5

        # indivisible sequence → warn-once fallback, numerics unchanged
        from repro.core.backend import get_backend
        bk = get_backend("sharded")
        q3, k3, v3 = q[:, :192], k[:, :192], v[:, :192]   # 192/8 = 24, not ball-multiple
        with mesh_context(mesh):
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                o_sh = bk.ball(q3, k3, v3, None, ball_size=64)
            assert any("falls back" in str(x.message) for x in w), w
        o_ref = get_backend("jnp").ball(q3, k3, v3, None, ball_size=64)
        assert float(jnp.abs(o_sh - o_ref).max()) < 1e-6
        print("PARITY_OK")
    """)
    assert "PARITY_OK" in out


@pytest.mark.slow
def test_sharded_serve_decode_parity_8dev():
    """ServingEngine(backend="sharded") paged decode over row-partitioned
    KV pools generates the same tokens as the jnp engine."""
    out = _run("""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.configs.reduce import smoke_config
        from repro.models.api import model_api
        from repro.serving import ServingEngine
        from repro.distributed import mesh_context
        from repro.launch.mesh import make_local_mesh

        mcfg = smoke_config(get_config("tinyllama-1.1b")).scaled(n_layers=1)
        api = model_api(mcfg)
        params = api.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, mcfg.vocab_size, n, dtype=np.int32)
                   for n in (40, 70, 20)]
        ref_eng = ServingEngine(api, params, batch_slots=2, max_len=128,
                                paged=True, backend="jnp")
        ref = ref_eng.serve(prompts, max_new_tokens=6)
        with mesh_context(make_local_mesh()):
            eng = ServingEngine(api, params, batch_slots=2, max_len=128,
                                paged=True, backend="sharded")
        # pools divide the 8-way axis after the constructor's bump
        p = 8
        assert ((eng.num_blocks + 1) * eng.page) % p == 0
        res = eng.serve(prompts, max_new_tokens=6)   # outside the with-block
        eng.kv.check()
        for i in range(len(prompts)):
            np.testing.assert_array_equal(res[i], ref[i], err_msg=f"req {i}")
        print("SERVE_PARITY_OK")
    """)
    assert "SERVE_PARITY_OK" in out


# ---------------------------------------------------------------------------
# ring context parallelism (8 fake devices, subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ring_flash_parity_8dev():
    """ring_flash (causal + non-causal, ragged key mask) vs the unsharded
    jnp oracle: fwd AND full grads within atol 1e-5, with the causal hop
    table skipping ~half the hops."""
    out = _run("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.backend import get_backend
        from repro.distributed import ring
        from repro.launch.mesh import make_local_mesh
        from repro.kernels import occupancy
        from repro.numerics import key_padding_bias

        mesh, axis, p = make_local_mesh(8), "data", 8
        rng = np.random.default_rng(0)
        B, N, Hq, Hkv, D = 2, 128, 4, 2, 16
        q = jnp.asarray(rng.normal(size=(B, N, Hq, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, N, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, N, Hkv, D)), jnp.float32)
        mask = jnp.asarray(rng.random((B, N)) > 0.2)
        kb = key_padding_bias(mask, B, N)
        jb = get_backend("jnp")
        seq = P(None, axis)

        for causal in (True, False):
            live = occupancy.ring_hop_live(p, N // p, causal=causal)
            assert live.sum() == (p * (p + 1) // 2 if causal else p * p)

            def run(q, k, v):
                body = lambda q, k, v, kb: ring.ring_flash(
                    q, k, v, kb, axis=axis, p=p, causal=causal, live=live)
                return shard_map(body, mesh=mesh,
                                 in_specs=(seq, seq, seq, seq),
                                 out_specs=seq, check_vma=False)(q, k, v, kb)

            ref = jb.flash(q, k, v, key_valid=mask, causal=causal)
            e = float(jnp.abs(run(q, k, v) - ref).max())
            w = jnp.asarray(np.random.default_rng(1).normal(size=ref.shape))
            g1 = jax.grad(lambda q, k, v: (run(q, k, v) * w).sum(),
                          argnums=(0, 1, 2))(q, k, v)
            g2 = jax.grad(lambda q, k, v: (jb.flash(
                q, k, v, key_valid=mask, causal=causal) * w).sum(),
                argnums=(0, 1, 2))(q, k, v)
            ge = max(float(jnp.abs(a - b).max()) for a, b in zip(g1, g2))
            print("causal", causal, "fwd", e, "grad", ge)
            assert e < 1e-5 and ge < 1e-5, (causal, e, ge)
        print("RING_FLASH_OK")
    """)
    assert "RING_FLASH_OK" in out


@pytest.mark.slow
def test_ring_selection_parity_8dev():
    """ring_selection (sharded+rotating selection K/V, indices re-based to
    ring-local coordinates) vs the replicated jnp oracle, fwd + grads."""
    out = _run("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.backend import get_backend
        from repro.distributed import ring
        from repro.launch.mesh import make_local_mesh

        axis = "data"
        jb = get_backend("jnp")
        seq = P(None, axis)
        # (p, B, N, ell, g): the second mesh puts an EMPTY group (no valid
        # selection, like NSA's first causal group) beside live ones on a
        # shard — its backward once divided by tiny**2 and returned NaN
        for p, B, N, ell, g in ((8, 2, 128, 8, 16), (4, 1, 256, 8, 8)):
            mesh = make_local_mesh(p)
            rng = np.random.default_rng(0)
            Hq, Hkv, D, k_star = 4, 2, 16, 4
            G, nb = N // g, N // ell
            q = jnp.asarray(rng.normal(size=(B, N, Hq, D)), jnp.float32)
            k = jnp.asarray(rng.normal(size=(B, N, Hkv, D)), jnp.float32)
            v = jnp.asarray(rng.normal(size=(B, N, Hkv, D)), jnp.float32)
            mask = jnp.asarray(rng.random((B, N)) > 0.2)
            ti = jnp.asarray(rng.integers(0, nb, size=(B, G, Hkv, k_star)),
                             jnp.int32)
            sv = jnp.asarray(rng.random((B, G, Hkv, k_star)) > 0.25)
            if p == 4:
                sv = sv.at[:, 0].set(False)

            def run(q, k, v):
                body = lambda q, ti, sv, k, v, m, qv: ring.ring_selection(
                    q, k, v, ti, sv, m, qv, axis=axis, p=p,
                    block_size=ell, group_size=g)
                return shard_map(body, mesh=mesh,
                                 in_specs=(seq,) * 7, out_specs=seq,
                                 check_vma=False)(q, ti, sv, k, v, mask, mask)

            ref = jb.selection(q, k, v, ti, sv, mask, block_size=ell,
                               group_size=g)
            e = float(jnp.abs(run(q, k, v) - ref).max())
            w = jnp.asarray(np.random.default_rng(1).normal(size=ref.shape))
            g1 = jax.grad(lambda q, k, v: (run(q, k, v) * w).sum(),
                          argnums=(0, 1, 2))(q, k, v)
            g2 = jax.grad(lambda q, k, v: (jb.selection(
                q, k, v, ti, sv, mask, block_size=ell, group_size=g) * w).sum(),
                argnums=(0, 1, 2))(q, k, v)
            ge = max(float(jnp.abs(a - b).max()) for a, b in zip(g1, g2))
            print(p, "fwd", e, "grad", ge)
            assert all(bool(jnp.isfinite(x).all()) for x in g1)
            assert e < 1e-5 and ge < 1e-5, (p, e, ge)
        print("RING_SEL_OK")
    """)
    assert "RING_SEL_OK" in out


@pytest.mark.slow
def test_segment_sharded_varlen_parity_8dev():
    """All four packed-varlen ops on the sharded backend (LPT segment
    re-layout, zero collectives) vs the unsharded jnp oracle — fwd + a
    grad probe, with NO falls-back warning on divisible sizes."""
    out = _run("""
        import warnings
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.core.backend import get_backend
        from repro.distributed import mesh_context
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(8)
        rng = np.random.default_rng(0)
        T, Hq, Hkv, D = 512, 4, 2, 16
        offs = (0, 256, 320, 448, 512)
        offsets = jnp.asarray(offs, jnp.int32)
        q = jnp.asarray(rng.normal(size=(T, Hq, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(T, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(T, Hkv, D)), jnp.float32)
        m = jnp.asarray(rng.random(T) > 0.1)
        jb, sb = get_backend("jnp"), get_backend("sharded")
        ell, g, k_star, ball = 8, 16, 4, 64
        k_off = offsets // ell
        kc = jnp.asarray(rng.normal(size=(T // ell, Hkv, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(T // ell, Hkv, D)), jnp.float32)
        blkv = jnp.asarray(rng.random(T // ell) > 0.1)
        Gv = T // g
        so = np.searchsorted(np.asarray(offs)[1:], np.arange(Gv) * g, "right")
        lo = np.asarray(offs)[so] // ell
        span = np.maximum(np.asarray(offs)[so + 1] // ell - lo, 1)
        ti = jnp.asarray(lo[:, None, None] + rng.integers(
            0, 1000, size=(Gv, Hkv, k_star)) % span[:, None, None], jnp.int32)
        sv = jnp.asarray(rng.random((Gv, Hkv, k_star)) > 0.25)

        cases = [
            ("ball", lambda b: b.ball_varlen(q, k, v, offsets, m,
                                             ball_size=ball)),
            ("flash", lambda b: b.flash_varlen(q, kc, vc, offsets, k_off,
                                               key_valid=blkv)),
            ("window", lambda b: b.local_window_varlen(q, k, v, offsets,
                                                       window=32, mask=m)),
            ("sel", lambda b: b.selection_varlen(q, k, v, ti, sv, offsets,
                                                 m, block_size=ell,
                                                 group_size=g)),
        ]
        with mesh_context(mesh):
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                for name, fn in cases:
                    e = float(jnp.abs(fn(sb) - fn(jb)).max())
                    print(name, e)
                    assert e < 1e-5, (name, e)
                gq1 = jax.grad(lambda q_: (sb.ball_varlen(
                    q_, k, v, offsets, m, ball_size=ball) ** 2).sum())(q)
            assert not any("falls back" in str(x.message) for x in w), \\
                [str(x.message) for x in w]
        gq2 = jax.grad(lambda q_: (jb.ball_varlen(
            q_, k, v, offsets, m, ball_size=ball) ** 2).sum())(q)
        assert float(jnp.abs(gq1 - gq2).max()) < 1e-5
        print("VARLEN_OK")
    """)
    assert "VARLEN_OK" in out


# ---------------------------------------------------------------------------
# LPT segment partitioner + warn-once keying (single device, pure logic)
# ---------------------------------------------------------------------------

def test_lpt_beats_round_robin_on_skew():
    from repro.distributed import plan_segments, round_robin_partition
    # skewed ragged batch: one giant segment + many small ones.  Cost is
    # quadratic in segment length, which round-robin's index-order deal
    # gets badly wrong.
    sizes = (512, 64, 64, 64, 64, 64, 64, 64, 64, 64)
    lpt = plan_segments(tuple(np.cumsum((0,) + sizes).tolist()), 4)
    rr = plan_segments(tuple(np.cumsum((0,) + sizes).tolist()), 4,
                       partition=round_robin_partition)
    # cost_balance = max shard load / mean load (1.0 = perfect)
    assert lpt.cost_balance < rr.cost_balance
    # LPT puts the giant segment alone on one shard
    giant_shard = lpt.assign[0]
    assert all(a != giant_shard for a in lpt.assign[1:])


def test_plan_segments_is_cached():
    from repro.distributed import plan_segments
    a = plan_segments((0, 128, 256), 2)
    b = plan_segments((0, 128, 256), 2)
    assert a is b


def test_warn_once_keys_on_op_and_reason():
    import warnings
    from repro.distributed.sharded_backend import _warn_once, reset_warnings
    reset_warnings()
    # two DISTINCT causes for one op must BOTH warn ...
    with pytest.warns(RuntimeWarning, match="indivisible-dim"):
        _warn_once("flash", "indivisible-dim", "seq 100 % 8 != 0")
    with pytest.warns(RuntimeWarning, match="causal-qk-mismatch"):
        _warn_once("flash", "causal-qk-mismatch", "N=1 != L=64")
    # ... while a repeat of the same (op, code) stays silent, even with a
    # different dynamic detail string
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _warn_once("flash", "indivisible-dim", "seq 204 % 8 != 0")
    reset_warnings()
