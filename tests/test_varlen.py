"""Packed-varlen (offsets-based) layout: packing, kernels, BSA, serving.

The load-bearing invariant mirrors tests/test_batching.py one level deeper:
a PACKED batch of mixed-size clouds — samples concatenated on one unbatched
axis with an ``offsets`` boundary array (docs/varlen.md) — equals running
every cloud alone AND equals the bucket-padded layout, forward and
gradients, on the jnp oracle and the Pallas kernel paths.  Nothing may leak
across a sample boundary on the packed axis.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BSAConfig,
    bsa_attention,
    bsa_attention_varlen,
    bsa_init,
    pack_ragged,
    pack_varlen,
    unpack_varlen,
    use_backend,
)
from repro.numerics import segment_ids_from_offsets

KEY = jax.random.PRNGKey(23)

# adversarial size mixes: prime-ish lengths, a singleton cloud, and a
# max-variance batch (largest next to smallest)
MIXES = [
    (20, 45, 33, 11),
    (64, 1, 37),
    (128, 16),
]


@pytest.fixture(autouse=True)
def _no_env_override(monkeypatch):
    monkeypatch.delenv("REPRO_ATTENTION_BACKEND", raising=False)


def _cfg(**kw):
    base = dict(ball_size=16, local_window=16, cmp_block=8, slc_block=8,
                top_k=2, group_size=8)
    base.update(kw)
    return BSAConfig(**base)


def _clouds(sizes, Hq=4, Hkv=2, D=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda n, h: rng.standard_normal((n, h, D)).astype(np.float32)
    return ([mk(n, Hq) for n in sizes], [mk(n, Hkv) for n in sizes],
            [mk(n, Hkv) for n in sizes])


def _pack(qs, ks, vs, multiple, **kw):
    qp, offs, mask = pack_varlen(qs, multiple, **kw)
    kp, _, _ = pack_varlen(ks, multiple, **kw)
    vp, _, _ = pack_varlen(vs, multiple, **kw)
    return (jnp.asarray(qp), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(offs), jnp.asarray(mask))


# ---------------------------------------------------------------------------
# host-side packing helpers
# ---------------------------------------------------------------------------

def test_pack_varlen_roundtrip():
    rng = np.random.default_rng(1)
    arrays = [rng.standard_normal((n, 5)).astype(np.float32) for n in (7, 30, 16)]
    packed, offsets, mask = pack_varlen(arrays, 16)
    # per-sample ball padding: 16 + 32 + 16 = 64 packed rows, capacity ≥ that
    assert offsets.tolist() == [0, 16, 48, 64]
    assert packed.shape[0] >= 64 and packed.shape[0] % 16 == 0
    assert mask.sum() == 7 + 30 + 16
    back = unpack_varlen(packed, offsets, mask)
    for a, b in zip(arrays, back):
        np.testing.assert_array_equal(a, b)
    # padding rows (within-sample and capacity tail) are the fill value
    assert np.all(packed[7:16] == 0.0) and np.all(packed[64:] == 0.0)


def test_pack_varlen_static_shapes():
    a = [np.zeros((20, 2), np.float32)]
    # max_samples pads offsets with trailing repeats (empty segments)
    packed, offsets, mask = pack_varlen(a, 16, max_samples=3)
    assert offsets.tolist() == [0, 32, 32, 32]
    back = unpack_varlen(packed, offsets, mask)
    assert [b.shape[0] for b in back] == [20, 0, 0]
    # pad_to freezes the capacity; must be a multiple and hold the total
    packed, _, _ = pack_varlen(a, 16, pad_to=64)
    assert packed.shape[0] == 64
    with pytest.raises(ValueError):
        pack_varlen(a, 16, pad_to=16)
    with pytest.raises(ValueError):
        pack_varlen(a, 16, pad_to=50)
    with pytest.raises(ValueError):
        pack_varlen(a * 4, 16, max_samples=3)


def test_segment_ids_from_offsets():
    offs = jnp.asarray([0, 16, 48, 64, 64], jnp.int32)   # trailing empty seg
    seg = segment_ids_from_offsets(offs, 80)
    assert seg.shape == (80,)
    assert int(seg[0]) == 0 and int(seg[15]) == 0
    assert int(seg[16]) == 1 and int(seg[47]) == 1
    assert int(seg[48]) == 2 and int(seg[63]) == 2
    # capacity tail gets an id strictly greater than every real segment,
    # and the empty segment (3) owns no positions
    assert np.all(np.asarray(seg[64:]) == 4)


# ---------------------------------------------------------------------------
# kernel wrappers vs the jnp oracle (fwd + grads)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", MIXES)
def test_flash_varlen_kernel_matches_oracle(sizes):
    from repro.core.backend import get_backend
    from repro.kernels import ops
    qs, ks, vs = _clouds(sizes)
    q, k, v, offs, mask = _pack(qs, ks, vs, 16)
    oracle = get_backend("jnp").flash_varlen

    def make_loss(fn):
        def loss(q, k, v):
            o = fn(q, k, v, offs, offs, key_valid=mask)
            return jnp.sum(jnp.where(mask[:, None, None], o, 0.0) ** 2)
        return loss

    out = ops.flash_attention_varlen(q, k, v, offs, offs, key_valid=mask)
    want = oracle(q, k, v, offs, offs, key_valid=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    g_k = jax.grad(make_loss(ops.flash_attention_varlen),
                   argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(make_loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_k, g_r, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3, err_msg=nm)
    # a masked (padding) key row gets exactly zero gradient
    pad_rows = ~np.asarray(mask)
    np.testing.assert_allclose(np.asarray(g_k[1])[pad_rows], 0.0, atol=1e-7)


def test_flash_varlen_no_cross_sample_leak():
    """Perturbing sample j must not change sample i ≠ j (kernel path)."""
    from repro.kernels import ops
    sizes = (32, 48)
    qs, ks, vs = _clouds(sizes)
    q, k, v, offs, mask = _pack(qs, ks, vs, 16)
    out = ops.flash_attention_varlen(q, k, v, offs, offs, key_valid=mask)
    k2 = k.at[int(offs[1]):].add(7.0)          # clobber sample 1's keys
    v2 = v.at[int(offs[1]):].add(-3.0)
    out2 = ops.flash_attention_varlen(q, k2, v2, offs, offs, key_valid=mask)
    np.testing.assert_array_equal(np.asarray(out[:sizes[0]]),
                                  np.asarray(out2[:sizes[0]]))
    assert np.abs(np.asarray(out2[int(offs[1]):int(offs[1]) + sizes[1]]
                             - out[int(offs[1]):int(offs[1]) + sizes[1]])).max() > 1e-3


@pytest.mark.parametrize("sizes", MIXES)
def test_local_varlen_kernel_matches_oracle(sizes):
    from repro.core.backend import get_backend
    from repro.kernels import ops
    w = 16
    qs, ks, vs = _clouds(sizes)
    q, k, v, offs, mask = _pack(qs, ks, vs, w)
    oracle = get_backend("jnp").local_window_varlen

    def make_loss(fn):
        def loss(q, k, v):
            o = fn(q, k, v, offs, window=w, mask=mask)
            return jnp.sum(jnp.where(mask[:, None, None], o, 0.0) ** 2)
        return loss

    out = ops.local_window_attention_varlen(q, k, v, offs, w, mask=mask)
    want = oracle(q, k, v, offs, window=w, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    g_k = jax.grad(make_loss(ops.local_window_attention_varlen),
                   argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(make_loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_k, g_r, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3, err_msg=nm)


def test_local_varlen_window_does_not_cross_boundary():
    """First block of a segment must NOT see the previous segment's last
    block (which is adjacent on the packed axis)."""
    from repro.kernels import ops
    w = 16
    sizes = (16, 16)
    qs, ks, vs = _clouds(sizes, seed=5)
    q, k, v, offs, mask = _pack(qs, ks, vs, w)
    out = ops.local_window_attention_varlen(q, k, v, offs, w, mask=mask)
    k2 = k.at[:16].add(9.0)                    # clobber sample 0 entirely
    v2 = v.at[:16].add(9.0)
    out2 = ops.local_window_attention_varlen(q, k2, v2, offs, w, mask=mask)
    np.testing.assert_array_equal(np.asarray(out[16:32]),
                                  np.asarray(out2[16:32]))


# ---------------------------------------------------------------------------
# full BSA: packed == per-sample == bucket-padded (fwd + grads)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas", "interpret"])
@pytest.mark.parametrize("sizes", MIXES)
def test_bsa_varlen_equals_per_sample(backend, sizes):
    cfg = _cfg(backend=backend)
    qs, ks, vs = _clouds(sizes)
    q, k, v, offs, mask = _pack(qs, ks, vs, cfg.ball_size)
    params = bsa_init(jax.random.fold_in(KEY, 1), cfg, n_heads=4, n_kv_heads=2,
                      head_dim=16, d_model=64)
    atol = 1e-5 if backend == "jnp" else 1e-3

    out_p = bsa_attention_varlen(params, q, k, v, cfg=cfg, offsets=offs,
                                 mask=mask)
    for i, n in enumerate(sizes):
        q1, m1 = pack_ragged([qs[i]], cfg.ball_size, geometric=False)
        k1, _ = pack_ragged([ks[i]], cfg.ball_size, geometric=False)
        v1, _ = pack_ragged([vs[i]], cfg.ball_size, geometric=False)
        solo = bsa_attention(params, jnp.asarray(q1), jnp.asarray(k1),
                             jnp.asarray(v1), cfg=cfg, mask=jnp.asarray(m1))
        a = int(offs[i])
        np.testing.assert_allclose(np.asarray(out_p[a:a + n]),
                                   np.asarray(solo[0][:n]),
                                   atol=atol, rtol=atol,
                                   err_msg=f"fwd sample {i} (n={n})")
    # padded rows (within-sample and capacity tail) are exactly zero
    np.testing.assert_allclose(
        np.asarray(out_p)[~np.asarray(mask)], 0.0, atol=1e-7)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_bsa_varlen_equals_bucket_padded_with_grads(backend):
    """Packed-varlen vs the padded-bucket layout of the SAME mixed batch:
    forward, loss, and all gradients agree."""
    sizes = (64, 40, 24)
    N = 64
    cfg = _cfg(backend=backend)
    qs, ks, vs = _clouds(sizes)
    params = bsa_init(jax.random.fold_in(KEY, 2), cfg, n_heads=4, n_kv_heads=2,
                      head_dim=16, d_model=64)
    atol = 1e-5 if backend == "jnp" else 1e-3

    # padded-bucket layout
    qb, maskb = pack_ragged(qs, cfg.ball_size, pad_to=N)
    kb, _ = pack_ragged(ks, cfg.ball_size, pad_to=N)
    vb, _ = pack_ragged(vs, cfg.ball_size, pad_to=N)
    qb, kb, vb, maskb = map(jnp.asarray, (qb, kb, vb, maskb))

    def loss_pad(p, q, k, v, m):
        return jnp.sum(bsa_attention(p, q, k, v, cfg=cfg, mask=m) ** 2)

    # packed-varlen layout
    qp, kp, vp, offs, maskp = _pack(qs, ks, vs, cfg.ball_size)

    def loss_pk(p, q, k, v, m):
        return jnp.sum(bsa_attention_varlen(p, q, k, v, cfg=cfg, offsets=offs,
                                            mask=m) ** 2)

    l_pad, g_pad = jax.value_and_grad(loss_pad)(params, qb, kb, vb, maskb)
    l_pk, g_pk = jax.value_and_grad(loss_pk)(params, qp, kp, vp, maskp)
    np.testing.assert_allclose(float(l_pk), float(l_pad), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_pk), jax.tree.leaves(g_pad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=atol, rtol=1e-3)
    # input grads agree per sample (packed rows vs padded slots)
    gq_pk, gk_pk = jax.grad(loss_pk, argnums=(1, 2))(params, qp, kp, vp, maskp)
    gq_pad, gk_pad = jax.grad(loss_pad, argnums=(1, 2))(params, qb, kb, vb,
                                                        maskb)
    for i, n in enumerate(sizes):
        a = int(offs[i])
        np.testing.assert_allclose(np.asarray(gq_pk[a:a + n]),
                                   np.asarray(gq_pad[i, :n]),
                                   atol=atol, rtol=1e-3, err_msg=f"dq {i}")
        np.testing.assert_allclose(np.asarray(gk_pk[a:a + n]),
                                   np.asarray(gk_pad[i, :n]),
                                   atol=atol, rtol=1e-3, err_msg=f"dk {i}")


def test_bsa_varlen_backend_fallback():
    """A plug-in backend WITHOUT varlen ops serves packed batches through
    the jnp oracle via get_varlen (same fallback contract as get_combine)."""
    from repro.core.backend import JnpBackend, get_varlen

    class Minimal:
        name = "minimal"
        ball = JnpBackend.ball
        flash = JnpBackend.flash
        local_window = JnpBackend.local_window
        selection = JnpBackend.selection

    fn = get_varlen(Minimal(), "flash")
    assert fn.__self__.name == "jnp"           # bound to the jnp oracle
    assert callable(get_varlen(Minimal(), "ball"))


# ---------------------------------------------------------------------------
# model + serving integration
# ---------------------------------------------------------------------------

def test_geometry_engine_packed_matches_padded():
    import dataclasses

    from repro.configs import get_config
    from repro.models.api import model_api
    from repro.serving import GeometryEngine

    mcfg = get_config("shapenet-bsa").scaled(
        n_layers=2, d_model=32, n_heads=2, head_dim=16, n_kv_heads=2, d_ff=64)
    mcfg = mcfg.scaled(bsa=dataclasses.replace(mcfg.bsa, ball_size=16,
                                               local_window=16))
    api = model_api(mcfg)
    params = api.init(jax.random.PRNGKey(0))
    eng_pk = GeometryEngine(api, params, batch_slots=3)
    assert eng_pk.layout == "packed"           # auto default for BSA
    eng_pad = GeometryEngine(api, params, batch_slots=3, layout="padded")

    rng = np.random.default_rng(7)
    clouds = []
    for n in (20, 45, 33, 11):                 # short final batch too
        pts = rng.standard_normal((n, 3)).astype(np.float32)
        feats = rng.standard_normal((n, mcfg.in_dim)).astype(np.float32)
        clouds.append((pts, feats))

    out_pk = eng_pk.predict(clouds)
    out_pad = eng_pad.predict(clouds)
    assert [o.shape for o in out_pk] == [(20, 1), (45, 1), (33, 1), (11, 1)]
    for a, b in zip(out_pk, out_pad):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_geometry_engine_selection_replay():
    """Per-cloud selection ids are layout-free: the packed engine's ids,
    replayed by the padded one, are a top-k there too (gap 0, no flips) and
    give the same predictions; replaying wrong blocks shows in the gap."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.api import model_api
    from repro.serving import GeometryEngine

    mcfg = get_config("shapenet-bsa").scaled(
        n_layers=2, d_model=32, n_heads=2, head_dim=16, n_kv_heads=2, d_ff=64)
    mcfg = mcfg.scaled(bsa=dataclasses.replace(mcfg.bsa, ball_size=16,
                                               local_window=16, backend="jnp"))
    api = model_api(mcfg)
    params = api.init(jax.random.PRNGKey(0))
    eng_pk = GeometryEngine(api, params, batch_slots=3)
    eng_pad = GeometryEngine(api, params, batch_slots=1, layout="padded",
                             pad_to=64)
    rng = np.random.default_rng(7)
    clouds = [(rng.standard_normal((n, 3)).astype(np.float32),
               rng.standard_normal((n, mcfg.in_dim)).astype(np.float32))
              for n in (40, 64, 33, 50)]

    out_pk, sel_pk = eng_pk.predict(clouds, return_selection=True)
    for a, b in zip(out_pk, eng_pk.predict(clouds)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
    for (_, f), sel in zip(clouds, sel_pk):
        n_groups = -(-len(f) // 16) * 16 // 8       # ball-padded length / ℓ
        assert sel["indices"].shape == (2, n_groups, 2, 4)
        assert set(sel) == {"indices"}
    _, sel_pad = eng_pad.predict(clouds, return_selection=True)
    out_re, sel_re = eng_pad.predict(clouds, select=sel_pk)
    for a, b, s_pk, s_pad, s_re in zip(out_pk, out_re, sel_pk, sel_pad,
                                       sel_re):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.sort(s_pk["indices"], -1),
                                      np.sort(s_pad["indices"], -1))
        np.testing.assert_array_equal(s_re["indices"], s_pk["indices"])
        assert s_re["gap"].shape == (2,) and s_re["gap"].max() <= 1e-6
        assert s_re["flips"].sum() == 0

    # blocks one position over are not a top-k: the gap says so
    wrong = [{"indices": np.roll(s["indices"], 1, axis=1)} for s in sel_pk]
    _, sel_wrong = eng_pad.predict(clouds, select=wrong)
    assert max(s["gap"].max() for s in sel_wrong) > 1e-2
    assert sum(s["flips"].sum() for s in sel_wrong) > 0


def test_pc_model_offsets_path_matches_padded():
    """pc_apply with a packed row + offsets == bucket-padded rows."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.pointcloud import pc_apply, pc_init

    mcfg = get_config("shapenet-bsa").scaled(
        n_layers=2, d_model=32, n_heads=2, head_dim=16, n_kv_heads=2, d_ff=64)
    mcfg = mcfg.scaled(bsa=dataclasses.replace(mcfg.bsa, ball_size=16,
                                               local_window=16))
    params = pc_init(jax.random.PRNGKey(1), mcfg)
    rng = np.random.default_rng(9)
    sizes = (40, 17)
    feats = [rng.standard_normal((n, mcfg.in_dim)).astype(np.float32)
             for n in sizes]

    packed, offs, maskp = pack_varlen(feats, 16)
    with use_backend("jnp"):
        out_pk = pc_apply(params, jnp.asarray(packed)[None], mcfg=mcfg,
                          mask=jnp.asarray(maskp)[None],
                          offsets=jnp.asarray(offs))[0]
        for i, n in enumerate(sizes):
            f1, m1 = pack_ragged([feats[i]], 16, geometric=False)
            solo = pc_apply(params, jnp.asarray(f1), mcfg=mcfg,
                            mask=jnp.asarray(m1))[0][:n]
            a = int(offs[i])
            np.testing.assert_allclose(np.asarray(out_pk[a:a + n]),
                                       np.asarray(solo), atol=1e-5, rtol=1e-5)


def test_attention_layer_offsets_guards():
    import dataclasses

    from repro.configs import get_config
    from repro.models.attention_layer import attention_layer_apply, \
        attention_layer_init

    mcfg = get_config("shapenet-bsa").scaled(
        n_layers=1, d_model=32, n_heads=2, head_dim=16, n_kv_heads=2, d_ff=64)
    mcfg = mcfg.scaled(bsa=dataclasses.replace(mcfg.bsa, ball_size=16,
                                               local_window=16))
    p = attention_layer_init(jax.random.PRNGKey(0), mcfg,
                             param_dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32))
    offs = jnp.asarray([0, 16, 32], jnp.int32)
    with pytest.raises(ValueError):            # packed input must be B == 1
        attention_layer_apply(p, x, mcfg=mcfg, causal=False, offsets=offs)
    with pytest.raises(NotImplementedError):   # causal varlen not supported
        attention_layer_apply(p, x[:1], mcfg=mcfg, causal=True, offsets=offs)


# ---------------------------------------------------------------------------
# satellites: tuning-cache layout key, dataset deprecation
# ---------------------------------------------------------------------------

def test_tuning_cache_layout_key(tmp_path, monkeypatch):
    """Padded-bucket and packed-varlen launches of the same shape must NEVER
    share a tile cache entry — the layouts' cost profiles differ."""
    import json

    from repro.kernels import tuning

    monkeypatch.setenv(tuning.ENV_CACHE, str(tmp_path / "t.json"))
    tuning.clear_memory_cache()
    kw = dict(n_q=256, n_k=256, d=32, dtype=jnp.float32, interpret=True)
    k_pad = tuning._key("flash", variant="plain", **kw)
    k_pk = tuning._key("flash", variant="plain", layout="varlen", **kw)
    assert k_pad != k_pk and k_pk.endswith("/varlen")

    monkeypatch.setenv(tuning.ENV_AUTOTUNE, "1")
    tuning.get_tiles("flash", measure=lambda tq, tk: 1.0, variant="plain",
                     **kw)
    tuning.get_tiles("flash", measure=lambda tq, tk: 1.0, variant="plain",
                     layout="varlen", **kw)
    cache = json.loads((tmp_path / "t.json").read_text())
    assert k_pad in cache and k_pk in cache    # two distinct entries


def test_dataset_pad_to_deprecation():
    from repro.data import ShapeNetCarDataset
    ds = ShapeNetCarDataset("train", ball_size=32, n_points_range=(70, 120))
    with pytest.warns(DeprecationWarning, match="packed-varlen"):
        next(ds.batches(2, seed=0, pad_to=ds.max_padded_len))
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")         # no warning without pad_to
        next(ds.batches(2, seed=0))
