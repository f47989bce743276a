"""Gradient tests: jax.grad through each Pallas kernel vs the jnp reference.

The kernel path carries fused custom_vjp backward passes (FlashAttention-style
recomputation from logsumexp residuals); these tests assert that dQ/dK/dV —
and, end-to-end, parameter gradients of ``bsa_attention`` /
``nsa_causal_attention`` on the ``"pallas"`` backend — match the ``"jnp"``
reference backend to atol 1e-3.  Kernels run under interpret mode on CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BSAConfig, bsa_attention, bsa_init,
                        nsa_causal_attention, nsa_init)
from repro.core.branches import repeat_kv
from repro.kernels import ops, ref, selection
from selection_cases import SCHEDULES, SELECTION_CASES, selection_ids

KEY = jax.random.PRNGKey(123)
TOL = dict(atol=1e-3, rtol=1e-3)


@pytest.fixture(autouse=True)
def _no_env_override(monkeypatch):
    """These tests compare NAMED backends (pallas vs jnp); a CI matrix leg
    pinning REPRO_ATTENTION_BACKEND would collapse both sides to one backend
    and make the parity assertions vacuous."""
    monkeypatch.delenv("REPRO_ATTENTION_BACKEND", raising=False)


def _assert_grads_close(got, want):
    gl, wl = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(gl) == len(wl)
    for g, w in zip(gl, wl):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **TOL)


def _qkvw(B, N, Hq, Hkv, D, L=None):
    L = N if L is None else L
    ks = jax.random.split(KEY, 4)
    return (jax.random.normal(ks[0], (B, N, Hq, D)),
            jax.random.normal(ks[1], (B, L, Hkv, D)),
            jax.random.normal(ks[2], (B, L, Hkv, D)),
            jax.random.normal(ks[3], (B, N, Hq, D)))


def _mask(B, N, masked):
    if not masked:
        return None
    return jnp.ones((B, N), bool).at[:, -N // 8:].set(False)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("rep", [1, 4])
def test_ball_attention_grads(masked, rep):
    B, N, Hkv, D, m = 1, 128, 1, 32, 32
    q, k, v, w = _qkvw(B, N, Hkv * rep, Hkv, D)
    mask = _mask(B, N, masked)

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, repeat_kv(k, rep), repeat_kv(v, rep), mask, m) * w)
        return f

    got = jax.grad(loss(ops.ball_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref.ball_attention_ref), argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(got, want)


@pytest.mark.parametrize("rep", [1, 4])
def test_local_window_grads(rep):
    B, N, Hkv, D, w_blk = 1, 128, 1, 32, 32
    q, k, v, w = _qkvw(B, N, Hkv * rep, Hkv, D)

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, repeat_kv(k, rep), repeat_kv(v, rep), w_blk) * w)
        return f

    got = jax.grad(loss(ops.local_window_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref.local_window_attention_ref), argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(got, want)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("rep", [1, 4])
def test_flash_grads(masked, rep):
    B, N, L, Hkv, D = 1, 128, 128, 1, 32
    q, k, v, w = _qkvw(B, N, Hkv * rep, Hkv, D, L=L)
    kwargs = dict(key_valid=_mask(B, L, True)) if masked else {}

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, repeat_kv(k, rep), repeat_kv(v, rep), **kwargs) * w)
        return f

    got = jax.grad(loss(ops.flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref.flash_attention_ref), argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(got, want)


@pytest.mark.parametrize("mode", ["causal", "block_causal"])
def test_flash_causal_grads(mode):
    B, N, Hq, D = 1, 128, 2, 32
    if mode == "causal":
        L, kwargs = N, dict(causal=True)
    else:
        L, kwargs = 16, dict(block_causal=True, ell=N // 16)
    q, k, v, w = _qkvw(B, N, Hq, Hq, D, L=L)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, **kwargs) * w)

    got = jax.grad(loss(ops.flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref.flash_attention_ref), argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(got, want)


@pytest.mark.parametrize("rep,masked,case,schedule", [
    pytest.param(rep, masked, "random", None, id=f"{rep}-{masked}")
    for rep in (1, 4) for masked in (False, True)
] + [
    # no padding in "chunked": the partial last chunk's last group stays live
    pytest.param(4 if case == "gqa4" else 1, case != "chunked", case, schedule,
                 id=f"{case}-{schedule}")
    for case in SELECTION_CASES for schedule in SCHEDULES
])
def test_selection_grads(rep, masked, case, schedule, use_selection_schedule):
    """dQ/dK/dV against the oracle's; the named cases cover both schedules:
    one block summed over every group, duplicate ids, −1 and all-invalid
    groups, heavy key padding, GQA rep 4, L > N keys, a partial last chunk."""
    use_selection_schedule(schedule, 6 if case == "chunked" else None)
    B = 2 if case == "shared" else 1
    N, Hkv, D, ell, g, ks = 128, 2, 32, 8, 8, 4
    L = 2 * N if case == "wide-keys" else N
    q, k, v, w = _qkvw(B, N, Hkv * rep, Hkv, D, L)
    G, nb = N // g, L // ell
    idx, valid = selection_ids(case, jax.random.fold_in(KEY, rep),
                               (B, G, Hkv, ks), nb)
    mask = _mask(B, L, masked)
    if case == "padding":
        mask = mask.at[:, -3 * L // 8:].set(False)
    if schedule is not None:
        assert selection.selection_schedule(
            "bwd", (B, Hkv, G, g * rep, D), (B, Hkv, nb, ell, D), ks,
            q.dtype) == schedule

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, k, v, idx, valid, mask,
                              block_size=ell, group_size=g) * w)
        return f

    got = jax.grad(loss(ops.selection_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref.selection_attention_ref), argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(got, want)


# ---------------------------------------------------------------------------
# End-to-end: jax.grad of the full attention stacks, kernels vs jnp reference
# ---------------------------------------------------------------------------

_E2E_CFG = dict(ball_size=32, local_window=32, cmp_block=8, slc_block=8,
                top_k=2, group_size=8)


@pytest.mark.parametrize("masked", [False, True])
def test_bsa_attention_grads_kernel_path(masked):
    B, N, Hq, Hkv, D, dm = 1, 128, 4, 2, 32, 64
    q, k, v, w = _qkvw(B, N, Hq, Hkv, D)
    mask = _mask(B, N, masked)
    cfg = BSAConfig(**_E2E_CFG)
    params = bsa_init(jax.random.fold_in(KEY, 7), cfg, n_heads=Hq,
                      n_kv_heads=Hkv, head_dim=D, d_model=dm)

    def loss(backend):
        c = dataclasses.replace(cfg, backend=backend)

        def f(params, q, k, v):
            return jnp.sum(bsa_attention(params, q, k, v, cfg=c, mask=mask) * w)
        return f

    got = jax.grad(loss("pallas"), argnums=(0, 1, 2, 3))(params, q, k, v)
    want = jax.grad(loss("jnp"), argnums=(0, 1, 2, 3))(params, q, k, v)
    _assert_grads_close(got, want)


def test_nsa_causal_attention_grads_kernel_path():
    B, N, Hq, Hkv, D, dm = 1, 128, 4, 2, 32, 64
    q, k, v, w = _qkvw(B, N, Hq, Hkv, D)
    cfg = BSAConfig(**_E2E_CFG)
    params = nsa_init(jax.random.fold_in(KEY, 8), cfg, n_heads=Hq,
                      n_kv_heads=Hkv, head_dim=D, d_model=dm)

    def loss(backend):
        c = dataclasses.replace(cfg, backend=backend)

        def f(params, q, k, v):
            return jnp.sum(nsa_causal_attention(params, q, k, v, cfg=c) * w)
        return f

    got = jax.grad(loss("pallas"), argnums=(0, 1, 2, 3))(params, q, k, v)
    want = jax.grad(loss("jnp"), argnums=(0, 1, 2, 3))(params, q, k, v)
    _assert_grads_close(got, want)


@pytest.mark.parametrize("kernel", ["selection", "local"])
def test_grads_finite_under_logit_blowup(kernel):
    """Regression: clamped fetches (invalid selection / last local block) must
    be masked in LOGIT space in the backward — exp-then-zero gives inf·0=NaN
    once a clamped logit exceeds the row's lse (large-magnitude q/k, as in
    attention-logit blowup during training)."""
    B, N, Hkv, D = 1, 64, 1, 32
    q, k, v, w = _qkvw(B, N, Hkv, Hkv, D)
    q, k = q * 30, k * 30
    if kernel == "selection":
        ell, g, ks = 8, 8, 4
        G, nb = N // g, N // ell
        k1, k2 = jax.random.split(KEY)
        idx = jax.random.randint(k1, (B, G, Hkv, ks), 0, nb)
        valid = jax.random.bernoulli(k2, 0.5, (B, G, Hkv, ks))

        def kfn(q, k, v):
            return jnp.sum(ops.selection_attention(
                q, k, v, idx, valid, None, block_size=ell, group_size=g) * w)

        def rfn(q, k, v):
            return jnp.sum(ref.selection_attention_ref(
                q, k, v, idx, valid, None, block_size=ell, group_size=g) * w)
    else:
        def kfn(q, k, v):
            return jnp.sum(ops.local_window_attention(q, k, v, 32) * w)

        def rfn(q, k, v):
            return jnp.sum(ref.local_window_attention_ref(q, k, v, 32) * w)

    got = jax.grad(kfn, argnums=(0, 1, 2))(q, k, v)
    assert all(bool(jnp.isfinite(g).all()) for g in got)
    _assert_grads_close(got, jax.grad(rfn, argnums=(0, 1, 2))(q, k, v))


# ---------------------------------------------------------------------------
# Tiered-tolerance dtype sweep: the precision contract (bf16 matmul operands,
# fp32 accumulation) across every kernel, kernel-vs-oracle grads.  fp32 keeps
# the strict 1e-3 tolerance; bf16 tolerances are widened PER KERNEL — bf16 has
# ~3 decimal digits, and error compounds with the number of chained matmuls
# (selection re-gathers, local merges two softmax halves).
# ---------------------------------------------------------------------------

_DTYPE_TOL = {
    "float32": {k: dict(atol=1e-3, rtol=1e-3)
                for k in ("ball", "local", "flash", "selection")},
    "bfloat16": {"ball": dict(atol=4e-2, rtol=4e-2),
                 "local": dict(atol=4e-2, rtol=4e-2),
                 "flash": dict(atol=4e-2, rtol=4e-2),
                 "selection": dict(atol=6e-2, rtol=6e-2)},
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", ["ball", "local", "flash", "selection"])
def test_grad_parity_dtype_sweep(kernel, dtype):
    tol = _DTYPE_TOL[dtype][kernel]
    B, N, Hkv, D = 1, 128, 2, 32
    rep = 2
    q, k, v, w = _qkvw(B, N, Hkv * rep, Hkv, D)
    dt = jnp.dtype(dtype)
    q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    mask = _mask(B, N, True)

    if kernel == "ball":
        kfn = lambda q, k, v: ops.ball_attention(q, k, v, mask, 32)
        rfn = lambda q, k, v: ref.ball_attention_ref(
            q, repeat_kv(k, rep), repeat_kv(v, rep), mask, 32)
    elif kernel == "local":
        kfn = lambda q, k, v: ops.local_window_attention(q, k, v, 32, mask)
        rfn = lambda q, k, v: ref.local_window_attention_ref(
            q, repeat_kv(k, rep), repeat_kv(v, rep), 32, mask)
    elif kernel == "flash":
        kfn = lambda q, k, v: ops.flash_attention(q, k, v, key_valid=mask)
        rfn = lambda q, k, v: ref.flash_attention_ref(
            q, repeat_kv(k, rep), repeat_kv(v, rep), key_valid=mask)
    else:
        ell, g, ks = 8, 8, 4
        G, nb = N // g, N // ell
        k1, k2 = jax.random.split(jax.random.fold_in(KEY, 21))
        idx = jax.random.randint(k1, (B, G, Hkv, ks), 0, nb)
        valid = jax.random.bernoulli(k2, 0.85, (B, G, Hkv, ks))
        kfn = lambda q, k, v: ops.selection_attention(
            q, k, v, idx, valid, mask, block_size=ell, group_size=g)
        rfn = lambda q, k, v: ref.selection_attention_ref(
            q, k, v, idx, valid, mask, block_size=ell, group_size=g)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                       * w)

    got = jax.grad(loss(kfn), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(rfn), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32), **tol)


@pytest.mark.parametrize("B,split", [(1, "heads"), (2, "heads"), (2, "batch")])
def test_selection_smem_split_parity(monkeypatch, B, split):
    """Launches split over (batch, KV head) so that each launch's ids fit
    SMEM give the unsplit kernel's result, forward and gradients."""
    from repro.kernels import selection

    N, Hkv, rep, D, ell, g, ks = 128, 2, 2, 32, 8, 8, 4
    G, nb = N // g, N // ell
    q, k, v, w = _qkvw(B, N, Hkv * rep, Hkv, D)
    mask = _mask(B, N, True)
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 22))
    idx = jax.random.randint(k1, (B, G, Hkv, ks), 0, nb)
    valid = jax.random.bernoulli(k2, 0.85, (B, G, Hkv, ks))

    def loss(q, k, v):
        out = ops.selection_attention(q, k, v, idx, valid, mask,
                                      block_size=ell, group_size=g)
        return jnp.sum(out * w)

    run = jax.value_and_grad(loss, argnums=(0, 1, 2))
    want = run(q, k, v)
    # budget of one KV head's ids ("heads") or of one sample's ("batch")
    budget = G * ks * (1 if split == "heads" else Hkv)
    monkeypatch.setattr(selection, "_SMEM_ID_WORDS", budget)
    assert selection._smem_chunks(B, Hkv, G * ks) == (
        1, 1 if split == "heads" else Hkv)
    selection.selection_attention_kernel_call.clear_cache()
    try:
        got = run(q, k, v)
    finally:
        selection.selection_attention_kernel_call.clear_cache()
    _assert_grads_close(got, want)
    ref_loss = lambda q, k, v: jnp.sum(ref.selection_attention_ref(
        q, k, v, idx, valid, mask, block_size=ell, group_size=g) * w)
    _assert_grads_close(got, jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
        q, k, v))


def test_kernel_train_step_is_jittable():
    """A jitted fwd+bwd step on the kernel path compiles and yields finite grads."""
    B, N, Hq, Hkv, D, dm = 1, 128, 4, 2, 32, 64
    q, k, v, w = _qkvw(B, N, Hq, Hkv, D)
    cfg = BSAConfig(backend="pallas", **_E2E_CFG)
    params = bsa_init(jax.random.fold_in(KEY, 9), cfg, n_heads=Hq,
                      n_kv_heads=Hkv, head_dim=D, d_model=dm)

    @jax.jit
    def step(params, q, k, v):
        def f(p):
            return jnp.sum(bsa_attention(p, q, k, v, cfg=cfg) * w)
        return jax.value_and_grad(f)(params)

    loss, grads = step(params, q, k, v)
    assert jnp.isfinite(loss)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
