"""Per-kernel allclose tests vs pure-jnp oracles, swept over shapes/dtypes.

Kernels execute under interpret=True on CPU (the container has no TPU);
the kernel bodies are identical to what runs on hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, selection
from selection_cases import SCHEDULES, SELECTION_CASES, selection_ids

KEY = jax.random.PRNGKey(42)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,N,H,D,m", [
    (1, 256, 2, 64, 64),
    (2, 512, 4, 32, 128),
    (1, 256, 1, 128, 256),
    (2, 128, 2, 64, 32),
])
def test_ball_attention(B, N, H, D, m, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (B, N, H, D), dtype)
    k = _rand(k2, (B, N, H, D), dtype)
    v = _rand(k3, (B, N, H, D), dtype)
    mask = jnp.ones((B, N), bool).at[:, -N // 8:].set(False)
    out = ops.ball_attention(q, k, v, mask, m)
    want = ref.ball_attention_ref(q, k, v, mask, m)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,N,H,D,w", [
    (1, 256, 2, 64, 64),
    (2, 512, 2, 32, 128),
    (1, 128, 4, 128, 32),
])
def test_local_window(B, N, H, D, w, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (B, N, H, D), dtype)
    k = _rand(k2, (B, N, H, D), dtype)
    v = _rand(k3, (B, N, H, D), dtype)
    out = ops.local_window_attention(q, k, v, w)
    want = ref.local_window_attention_ref(q, k, v, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["plain", "causal", "block_causal", "key_valid"])
@pytest.mark.parametrize("B,N,L,H,D", [
    (1, 256, 256, 2, 64),
    (2, 512, 64, 2, 64),     # skinny KV (compression-branch shape)
    (1, 384, 48, 1, 128),    # non-power-of-two tiles
])
def test_flash(B, N, L, H, D, mode, dtype):
    if mode == "causal" and L != N:
        pytest.skip("token-causal assumes aligned q/k")
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (B, N, H, D), dtype)
    k = _rand(k2, (B, L, H, D), dtype)
    v = _rand(k3, (B, L, H, D), dtype)
    kwargs = {}
    if mode == "causal":
        kwargs = dict(causal=True)
    elif mode == "block_causal":
        kwargs = dict(block_causal=True, ell=N // L)
    elif mode == "key_valid":
        kwargs = dict(key_valid=jnp.ones((B, L), bool).at[:, -L // 4:].set(False))
    out = ops.flash_attention(q, k, v, **kwargs)
    want = ref.flash_attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# shapes of the named selection cases (conftest.selection_ids)
_SEL_CASE_SHAPES = {"shared": (2, 128, 2, 2, 32, 8, 8, 4),
                    "gqa4": (1, 128, 8, 2, 32, 8, 8, 4),
                    "chunked": (1, 128, 4, 2, 32, 8, 8, 4)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,N,Hq,Hkv,D,ell,g,ks,case,schedule", [
    pytest.param(1, 256, 2, 1, 64, 8, 8, 4, "random", None, id="1-256-2-1-64-8-8-4"),
    pytest.param(2, 512, 4, 2, 64, 8, 16, 4, "random", None, id="2-512-4-2-64-8-16-4"),
    # MHA, bigger blocks
    pytest.param(1, 256, 4, 4, 32, 16, 16, 2, "random", None, id="1-256-4-4-32-16-16-2"),
    # high GQA rep
    pytest.param(1, 128, 8, 2, 64, 8, 8, 6, "random", None, id="1-128-8-2-64-8-8-6"),
] + [
    pytest.param(*_SEL_CASE_SHAPES.get(case, (1, 128, 2, 1, 32, 8, 8, 4)), case,
                 schedule, id=f"{case}-{schedule}")
    for case in SELECTION_CASES for schedule in SCHEDULES
])
def test_selection(B, N, Hq, Hkv, D, ell, g, ks, case, schedule, dtype,
                   use_selection_schedule):
    use_selection_schedule(schedule, 6 if case == "chunked" else None)
    L = 2 * N if case == "wide-keys" else N        # a context shard's gathered keys
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = _rand(k1, (B, N, Hq, D), dtype)
    k = _rand(k2, (B, L, Hkv, D), dtype)
    v = _rand(k3, (B, L, Hkv, D), dtype)
    G, nb = N // g, L // ell
    idx, valid = selection_ids(case, k4, (B, G, Hkv, ks), nb)
    pad = 3 * L // 8 if case == "padding" else L // 8
    mask = jnp.ones((B, L), bool).at[:, -pad:].set(False)
    if schedule is not None:
        assert selection.selection_schedule(
            "fwd", (B, Hkv, G, g * Hq // Hkv, D), (B, Hkv, nb, ell, D), ks,
            dtype) == schedule
    out = ops.selection_attention(q, k, v, idx, valid, mask, block_size=ell, group_size=g)
    want = ref.selection_attention_ref(q, k, v, idx, valid, mask, block_size=ell, group_size=g)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("cell,batch,points,schedule", [
    ("train", 8, 3840, "resident"),
    ("serve-single", 1, 3840, "resident"),
    ("serve-batch", 1, 8 * 3840, "resident"),     # 8 clouds on one packed axis
    ("large", 1, 65536, "blocked"),
])
def test_selection_schedule_follows_shape(cell, batch, points, schedule,
                                          use_selection_schedule, monkeypatch):
    """The shapenet-bsa cells' selection launches (8 KV heads x 32, ℓ 8,
    top-k 4, group 8, fp32) pick their schedule from shape and dtype alone,
    and the launch runs under that schedule's scope."""
    H, D, ell, k_star, g = 8, 32, 8, 4, 8
    q = (batch, H, points // g, g, D)
    kb = (batch, H, points // ell, ell, D)
    for direction in ("fwd", "bwd"):
        assert selection.selection_schedule(
            direction, q, kb, k_star, jnp.float32) == schedule
    if cell == "large":       # the budget decides: raised, the slab fits
        monkeypatch.setattr(selection, "_RESIDENT_VMEM_BYTES", 512 << 20)
        assert selection.selection_schedule(
            "bwd", q, kb, k_star, jnp.float32) == "resident"
        monkeypatch.undo()

    use_selection_schedule(schedule)
    n = 64
    x = jnp.ones((1, n, 2, D))
    idx = jnp.zeros((1, n // g, 2, k_star), jnp.int32)
    f = lambda q, k, v: jnp.sum(ops.selection_attention(
        q, k, v, idx, idx >= 0, None, block_size=ell, group_size=g))
    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(x, x, x).as_text(
        debug_info=True)
    for kernel in ("bsa_selection_fwd", "bsa_selection_bwd"):
        assert f'"{schedule}/{kernel}/pallas_call"' in text, kernel


def test_selection_all_invalid_group_is_zero():
    B, N, Hq, Hkv, D, ell, g, ks = 1, 128, 2, 1, 32, 8, 8, 4
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (B, N, Hq, D), jnp.float32)
    k = _rand(k2, (B, N, Hkv, D), jnp.float32)
    v = _rand(k3, (B, N, Hkv, D), jnp.float32)
    idx = jnp.zeros((B, N // g, Hkv, ks), jnp.int32)
    valid = jnp.zeros((B, N // g, Hkv, ks), bool).at[:, 1:].set(True)
    out = ops.selection_attention(q, k, v, idx, valid, None, block_size=ell, group_size=g)
    assert not bool(jnp.isnan(out).any())
    np.testing.assert_allclose(np.asarray(out[:, :g]), 0.0, atol=1e-6)


def test_flash_matches_full_attention_einsum():
    """flash kernel == plain softmax attention (independent oracle)."""
    B, N, H, D = 1, 256, 2, 64
    k1, k2, k3 = jax.random.split(KEY, 3)
    q, k, v = (_rand(kk, (B, N, H, D), jnp.float32) for kk in (k1, k2, k3))
    out = ops.flash_attention(q, k, v, causal=True)
    logits = jnp.einsum("bnhd,bmhd->bhnm", q, k) / (D ** 0.5)
    mask = jnp.tril(jnp.ones((N, N), bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    want = jnp.einsum("bhnm,bmhd->bnhd", jax.nn.softmax(logits, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
