"""Tile-autotuner, tile-padding and fused-epilogue tests.

Covers the three new kernel-layer seams:

  * ``kernels/tuning.py`` — the deterministic heuristic (never degenerates
    to tiny tiles), the JSON cache round-trip (second lookup measures
    NOTHING), and the autotune-off fallback;
  * the flash wrapper's pad-to-tile contract — non-divisor axis lengths are
    padded (masked keys / sliced query rows) instead of shrinking the tile,
    with exact parity and zero gradient leakage into the pad;
  * ``ops.gated_combine`` — the fused gate epilogue vs the jnp reference,
    forward and gradients, scalar- and token-mode gate shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.branches import gated_combine_ref, repeat_kv
from repro.kernels import ops, ref, tuning

KEY = jax.random.PRNGKey(99)
TOL = dict(atol=1e-5, rtol=1e-5)


@pytest.fixture(autouse=True)
def _tuning_sandbox(tmp_path, monkeypatch):
    """Point the tuning cache at a throwaway file and reset memory state."""
    monkeypatch.setenv(tuning.ENV_CACHE, str(tmp_path / "tuning.json"))
    monkeypatch.delenv(tuning.ENV_AUTOTUNE, raising=False)
    tuning.clear_memory_cache()
    yield
    tuning.clear_memory_cache()


# ---------------------------------------------------------------------------
# heuristic
# ---------------------------------------------------------------------------

def test_heuristic_tile_never_degenerates():
    # primes / ragged leftovers used to collapse the divisor rule to tile 1
    for n in (257, 263, 131, 97, 1000, 1536, 520):
        t = tuning.heuristic_tile(n, 256)
        assert t % 8 == 0
        assert t >= min(tuning.round_up(n, 8), 256) // 2
        assert t <= max(256, tuning.round_up(n, 8))


def test_heuristic_tile_small_axis_pads_to_sublane():
    assert tuning.heuristic_tile(4, 256) == 8      # pad up, don't shrink
    assert tuning.heuristic_tile(48, 256) == 48
    assert tuning.heuristic_tile(256, 256) == 256
    assert tuning.heuristic_tile(512, 256) == 256  # exact divisor kept


def test_heuristic_tile_is_lane_aligned_past_one_tile():
    """Mosaic takes a block's last dim only as a multiple of 128 or the
    whole axis, and the key-bias / lse blocks carry the tile there: the
    compression branch's 480 pooled keys once got a 240 tile."""
    assert tuning.heuristic_tile(480, 256) == 256
    for n in (257, 300, 480, 1000, 3840, 4097):
        assert tuning.heuristic_tile(n, 256) % tuning.LANE == 0
    assert tuning.clamp_tile(64, 300, interpret=False) == 128
    assert tuning.clamp_tile(64, 300, interpret=True) == 64    # test tiles stay
    assert tuning.clamp_tile(256, 100, interpret=False) == 104  # whole axis


def test_shape_bucket():
    assert tuning.shape_bucket(1) == 1
    assert tuning.shape_bucket(256) == 256
    assert tuning.shape_bucket(257) == 512


# ---------------------------------------------------------------------------
# cache round-trip
# ---------------------------------------------------------------------------

def test_autotune_cache_round_trip(monkeypatch):
    monkeypatch.setenv(tuning.ENV_AUTOTUNE, "1")
    calls = []

    def measure(tq, tk):
        calls.append((tq, tk))
        return 1.0 if (tq, tk) != (128, 256) else 0.5   # winner: (128, 256)

    kw = dict(n_q=300, n_k=300, d=32, dtype=jnp.float32, interpret=True)
    tiles = tuning.get_tiles("flash", measure=measure, **kw)
    assert tiles == (128, 256)
    assert calls, "first resolution must measure"
    n_first = len(calls)

    def boom(tq, tk):
        raise AssertionError("cache hit must not re-measure")

    # same bucket (any n in (256, 512]) → pure lookup, measure never invoked
    assert tuning.get_tiles("flash", measure=boom, **kw) == (128, 256)
    assert tuning.get_tiles("flash", measure=boom,
                            n_q=400, n_k=511, d=32, dtype=jnp.float32,
                            interpret=True) == (128, 256)
    assert len(calls) == n_first

    # the persisted JSON survives a cold in-memory state (fresh process)
    tuning.clear_memory_cache()
    assert tuning.get_tiles("flash", measure=boom, **kw) == (128, 256)
    assert tuning.cache_path().exists()


def test_variant_isolates_cache_entries(monkeypatch):
    """Flash mask modes do different in-kernel work — causal / block-causal /
    plain must never share a cache entry."""
    monkeypatch.setenv(tuning.ENV_AUTOTUNE, "1")
    kw = dict(n_q=300, n_k=300, d=32, dtype=jnp.float32, interpret=True)
    tuning.get_tiles("flash", variant="plain",
                     measure=lambda tq, tk: 1.0 if (tq, tk) != (64, 128) else 0.1,
                     **kw)
    got = tuning.get_tiles("flash", variant="causal",
                           measure=lambda tq, tk: 1.0 if (tq, tk) != (256, 256) else 0.1,
                           **kw)
    assert got == (256, 256)                        # measured, not plain's hit
    assert tuning.get_tiles("flash", variant="plain", measure=None,
                            **kw) == (64, 128)
    assert tuning.flash_variant(True, False, 1) == "causal"
    assert tuning.flash_variant(False, True, 8) == "blockcausal8"
    assert tuning.flash_variant(False, False, 1) == "plain"


def test_compute_and_layout_isolate_cache_entries(monkeypatch):
    """The guard for the precision contract + batch layouts: a tile tuned
    under one compute dtype (bf16/fp8 operands) or one layout (packed
    varlen vs padded) must NEVER be replayed for another — the cost profile
    differs, so the cached winner is invalid there.  fp32/default compute
    deliberately shares the pre-contract key (old entries stay valid)."""
    monkeypatch.setenv(tuning.ENV_AUTOTUNE, "1")
    kw = dict(n_q=300, n_k=300, d=32, dtype=jnp.float32, interpret=True)
    tuning.get_tiles("flash",
                     measure=lambda tq, tk: 1.0 if (tq, tk) != (64, 128) else 0.1,
                     **kw)
    # different compute dtype: fresh measurement, not the fp32 hit
    got = tuning.get_tiles("flash", compute="bfloat16",
                           measure=lambda tq, tk: 1.0 if (tq, tk) != (256, 256) else 0.1,
                           **kw)
    assert got == (256, 256)
    # different layout: fresh measurement too
    got = tuning.get_tiles("flash", layout="varlen",
                           measure=lambda tq, tk: 1.0 if (tq, tk) != (128, 128) else 0.1,
                           **kw)
    assert got == (128, 128)
    # fp8 compute isolated from bf16 AND fp32
    got = tuning.get_tiles("flash", compute="float8_e4m3fn",
                           measure=lambda tq, tk: 1.0 if (tq, tk) != (64, 256) else 0.1,
                           **kw)
    assert got == (64, 256)
    # all four entries still resolve independently with no re-measurement
    def boom(tq, tk):
        raise AssertionError("cache hit must not re-measure")
    assert tuning.get_tiles("flash", measure=boom, **kw) == (64, 128)
    assert tuning.get_tiles("flash", compute="bfloat16", measure=boom,
                            **kw) == (256, 256)
    assert tuning.get_tiles("flash", layout="varlen", measure=boom,
                            **kw) == (128, 128)
    assert tuning.get_tiles("flash", compute="float8_e4m3fn", measure=boom,
                            **kw) == (64, 256)
    # compute="float32" IS the default key — pre-contract entries stay valid
    assert tuning.get_tiles("flash", compute="float32", measure=boom,
                            **kw) == (64, 128)
    # the storage dtype is part of the key independently of compute
    got = tuning.get_tiles("flash", n_q=300, n_k=300, d=32,
                           dtype=jnp.bfloat16, interpret=True,
                           measure=lambda tq, tk: 1.0 if (tq, tk) != (512, 512) else 0.1)
    assert got == (512, 512)


def test_kernel_call_rejects_non_dividing_tiles():
    from repro.kernels.flash import flash_attention_kernel_call
    q = jnp.zeros((1, 1, 300, 16))
    k = v = jnp.zeros((1, 300, 16))
    bias = jnp.zeros((1, 300), jnp.float32)
    with pytest.raises(ValueError, match="tiles must divide"):
        flash_attention_kernel_call(q, k, v, bias, n_heads=1, tq=256, tk=300,
                                    interpret=True)


def test_autotune_off_uses_heuristic_and_writes_nothing():
    def boom(tq, tk):
        raise AssertionError("autotune off must not measure")

    tiles = tuning.get_tiles("flash", n_q=257, n_k=64, d=32,
                             dtype=jnp.float32, interpret=True, measure=boom)
    assert tiles == (tuning.heuristic_tile(257, 256),
                     tuning.heuristic_tile(64, 256))
    assert not tuning.cache_path().exists()


def test_unset_cache_env_keeps_tiles_in_memory(monkeypatch, tmp_path):
    """Without $REPRO_TUNING_CACHE nothing outside the caller's choice is
    read or written: measured tiles live in memory only."""
    monkeypatch.delenv(tuning.ENV_CACHE)
    monkeypatch.setenv(tuning.ENV_AUTOTUNE, "1")
    monkeypatch.setenv("HOME", str(tmp_path))
    assert tuning.cache_path() is None
    kw = dict(n_q=300, n_k=300, d=32, dtype=jnp.float32, interpret=True)
    got = tuning.get_tiles("flash", measure=lambda tq, tk: 1.0 if (tq, tk) != (
        128, 128) else 0.1, **kw)
    assert got == (128, 128)
    assert tuning.get_tiles("flash", measure=None, **kw) == (128, 128)
    assert not any(tmp_path.rglob("*"))


def test_tune_flash_end_to_end(monkeypatch):
    """The real measurement path: tiny shape, interpret mode, twice."""
    monkeypatch.setenv(tuning.ENV_AUTOTUNE, "1")
    kw = dict(n_q=64, n_k=64, d=16, dtype=jnp.float32, interpret=True,
              bh=1, iters=1)
    tiles = tuning.tune_flash(**kw)
    assert tiles[0] % 8 == 0 and tiles[1] % 8 == 0
    import json
    data = json.loads(tuning.cache_path().read_text())
    assert len(data) == 1
    before = dict(data)
    assert tuning.tune_flash(**kw) == tiles          # hit: no re-measure
    assert json.loads(tuning.cache_path().read_text()) == before


# ---------------------------------------------------------------------------
# flash wrapper padding (tile need not divide the axis any more)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,L,tq,tk", [
    (120, 40, 64, 32),     # both axes padded
    (128, 48, 256, 32),    # q single tile, k padded
    (72, 24, 16, 16),      # small odd-ish axes
])
def test_flash_padding_parity(N, L, tq, tk):
    B, H, D = 1, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, N, H, D))
    k = jax.random.normal(ks[1], (B, L, H, D))
    v = jax.random.normal(ks[2], (B, L, H, D))
    kv = jnp.ones((B, L), bool).at[:, -L // 4:].set(False)
    out = ops.flash_attention(q, k, v, key_valid=kv, tq=tq, tk=tk)
    want = ref.flash_attention_ref(q, k, v, key_valid=kv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


def test_flash_padding_grads_no_leak():
    """Gradients through the padded path match the unpadded reference —
    i.e. the pad rows/keys contribute exactly nothing."""
    B, N, L, H, D = 1, 72, 24, 1, 16
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, N, H, D))
    k = jax.random.normal(ks[1], (B, L, H, D))
    v = jax.random.normal(ks[2], (B, L, H, D))
    w = jax.random.normal(ks[3], (B, N, H, D))

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, **kw) * w)

    got = jax.grad(loss(ops.flash_attention, tq=16, tk=16),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref.flash_attention_ref), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# ops-level GQA: un-repeated K/V through the kernel wrappers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rep", [1, 2, 4])
@pytest.mark.parametrize("op", ["ball", "flash", "local"])
def test_gqa_wrappers_match_repeated_reference(op, rep):
    B, N, Hkv, D = 1, 128, 1, 32
    ks = jax.random.split(jax.random.fold_in(KEY, rep), 4)
    q = jax.random.normal(ks[0], (B, N, Hkv * rep, D))
    k = jax.random.normal(ks[1], (B, N, Hkv, D))
    v = jax.random.normal(ks[2], (B, N, Hkv, D))
    w = jax.random.normal(ks[3], (B, N, Hkv * rep, D))
    mask = jnp.ones((B, N), bool).at[:, -N // 8:].set(False)

    if op == "ball":
        kfn = lambda q, k, v: ops.ball_attention(q, k, v, mask, 32)
        rfn = lambda q, k, v: ref.ball_attention_ref(
            q, repeat_kv(k, rep), repeat_kv(v, rep), mask, 32)
    elif op == "flash":
        kfn = lambda q, k, v: ops.flash_attention(q, k, v, key_valid=mask)
        rfn = lambda q, k, v: ref.flash_attention_ref(
            q, repeat_kv(k, rep), repeat_kv(v, rep), key_valid=mask)
    else:
        kfn = lambda q, k, v: ops.local_window_attention(q, k, v, 32, mask)
        rfn = lambda q, k, v: ref.local_window_attention_ref(
            q, repeat_kv(k, rep), repeat_kv(v, rep), 32, mask=mask)

    np.testing.assert_allclose(np.asarray(kfn(q, k, v)),
                               np.asarray(rfn(q, k, v)), atol=1e-4, rtol=1e-4)
    got = jax.grad(lambda q, k, v: jnp.sum(kfn(q, k, v) * w),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(rfn(q, k, v) * w),
                    argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# fused gated-combine epilogue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gate_shape", ["scalar", "token"])
@pytest.mark.parametrize("masked", [False, True])
def test_gated_combine_parity(gate_shape, masked):
    B, N, H, D = 2, 48, 3, 16
    ks = jax.random.split(KEY, 7)
    outs = tuple(jax.random.normal(ks[i], (B, N, H, D)) for i in range(3))
    gshape = (1, 1, H, 1) if gate_shape == "scalar" else (B, N, H, 1)
    gates = tuple(jax.nn.sigmoid(jax.random.normal(ks[3 + i], gshape))
                  for i in range(3))
    mask = jnp.ones((B, N), bool).at[:, -N // 4:].set(False) if masked else None

    out = ops.gated_combine(outs, gates, mask)
    want = gated_combine_ref(outs, gates, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)

    def loss(fn):
        def f(outs, gates):
            return jnp.sum(fn(outs, gates, mask) ** 2)
        return f

    got = jax.grad(loss(ops.gated_combine), argnums=(0, 1))(outs, gates)
    ref_g = jax.grad(loss(gated_combine_ref), argnums=(0, 1))(outs, gates)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)
