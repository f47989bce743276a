"""Compile every Pallas kernel for a TPU v5e chip, forward and backward.

Interpret mode (every other kernel test) accepts kernels that the chip's
Mosaic compiler refuses: blocks whose last two dims are not (8, 128)
aligned, unsupported in-kernel relayouts, more SMEM or VMEM than the core
has.  These tests lower and compile the kernels for a DESCRIBED v5e chip
with the TPU compiler that ships with jaxlib, at the ``shapenet-bsa`` widths
(batch 4, 3840 points = 15 balls of 256, 8 heads x 32, compression and
selection block 8, top-k 4, group 8) and at a GQA causal LM's (``LM_BSA``,
8 query heads over 2 KV heads x 128), and check that the compiled program
holds the named ``tpu_custom_call`` of each kernel — so no jnp fallback or
interpreter stood in.  Nothing runs: a pass says the chip's compiler took
the kernel, not that its numbers are right (the interpret-mode parity tests
own that).

The topology is described inside a module fixture, never at import time:
only one process may load libtpu, and a test worker that loads it keeps it
until it exits.  All such compiles live in this one file so that they run in
one worker; they compile in the test's own process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, N, H, D = 4, 3840, 8, 32                  # shapenet-bsa at batch 4
BALL, ELL, TOP_K, GROUP = 256, 8, 4, 8       # PAPER_BSA
# a GQA causal LM at LM_BSA (window 256, ℓ 64, top-k 16, group 64): 4 query
# heads per KV head fuse into each kernel's matmul rows
LM_N, LM_HQ, LM_HKV, LM_D = 4096, 8, 2, 128
LM_WINDOW, LM_ELL, LM_TOP_K, LM_GROUP = 256, 64, 16, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _cases():
    """name → (fn, arg shapes/dtypes, number of differentiable args,
    kernel names the forward / backward program must hold)."""
    f32, i32, bool_ = jnp.float32, jnp.int32, jnp.bool_
    qkv = [((B, N, H, D), f32)] * 3
    L, G, T = N // ELL, N // GROUP, B * N
    lm_q, lm_kv = ((1, LM_N, LM_HQ, LM_D), f32), ((1, LM_N, LM_HKV, LM_D), f32)
    lm_cmp = ((1, LM_N // LM_ELL, LM_HKV, LM_D), f32)
    lm_sel = (1, LM_N // LM_GROUP, LM_HKV, LM_TOP_K)
    return {
        "ball": (lambda q, k, v, m: ops.ball_attention(q, k, v, m, BALL,
                                                       interpret=False),
                 qkv + [((B, N), bool_)], 3,
                 ("bsa_ball_fwd",), ("bsa_ball_bwd",)),
        # compression branch: N queries vs N/ℓ = 480 pooled keys
        "flash_cmp": (lambda q, k, v, m: ops.flash_attention(
                          q, k, v, key_valid=m, interpret=False),
                      [((B, N, H, D), f32), ((B, L, H, D), f32),
                       ((B, L, H, D), f32), ((B, L), bool_)], 3,
                      ("bsa_flash_fwd",), ("bsa_flash_dq", "bsa_flash_dkv")),
        "selection": (lambda q, k, v, ti, sv, m: ops.selection_attention(
                          q, k, v, ti, sv, m, block_size=ELL, group_size=GROUP,
                          interpret=False),
                      qkv + [((B, G, H, TOP_K), i32), ((B, G, H, TOP_K), bool_),
                             ((B, N), bool_)], 3,
                      ("bsa_selection_fwd",), ("bsa_selection_bwd",)),
        # the batch packed on one axis, as GeometryEngine serves it
        "varlen": (lambda q, k, v, o, m: ops.flash_attention_varlen(
                       q, k, v, o, o, key_valid=m, interpret=False),
                   [((T, H, D), f32)] * 3 + [((B + 1,), i32), ((T,), bool_)], 3,
                   ("bsa_varlen_fwd",), ("bsa_varlen_dq", "bsa_varlen_dkv")),
        "epilogue": (lambda a, b, c, g: ops.gated_combine(
                         (a, b, c), (g, g, g), None, interpret=False),
                     qkv + [((1, 1, H, 1), f32)], 4,
                     ("bsa_epilogue_fwd",), ("bsa_epilogue_bwd",)),
        "local": (lambda q, k, v, m: ops.local_window_attention(
                      q, k, v, LM_WINDOW, m, interpret=False),
                  qkv + [((B, N), bool_)], 3,
                  ("bsa_local_fwd",), ("bsa_local_bwd",)),
        "lm_local": (lambda q, k, v: ops.local_window_attention(
                         q, k, v, LM_WINDOW, interpret=False),
                     [lm_q, lm_kv, lm_kv], 3,
                     ("bsa_local_fwd",), ("bsa_local_bwd",)),
        "lm_flash_cmp": (lambda q, k, v: ops.flash_attention(
                             q, k, v, block_causal=True, ell=LM_ELL,
                             interpret=False),
                         [lm_q, lm_cmp, lm_cmp], 3,
                         ("bsa_flash_fwd",), ("bsa_flash_dq", "bsa_flash_dkv")),
        "lm_selection": (lambda q, k, v, ti, sv: ops.selection_attention(
                             q, k, v, ti, sv, None, block_size=LM_ELL,
                             group_size=LM_GROUP, interpret=False),
                         [lm_q, lm_kv, lm_kv, (lm_sel, i32), (lm_sel, bool_)],
                         3, ("bsa_selection_fwd",), ("bsa_selection_bwd",)),
        "lm_varlen": (lambda q, k, v, o: ops.flash_attention_varlen(
                          q, k, v, o, o, interpret=False),
                      [(s[1:], dt) for s, dt in (lm_q, lm_kv, lm_kv)]
                      + [((3,), i32)], 3,
                      ("bsa_varlen_fwd",), ("bsa_varlen_dq", "bsa_varlen_dkv")),
    }


def _compiled_kernels(fn, shapes, sharding) -> list[str]:
    """Names of the Mosaic kernel launches in the compiled program."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return [line.split("=")[0].strip().lstrip("%").rsplit(".", 1)[0]
            for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("kernel", ["ball", "flash_cmp", "selection", "local",
                                    "varlen", "epilogue", "lm_local",
                                    "lm_flash_cmp", "lm_selection", "lm_varlen"])
def test_kernel_compiles_for_v5e(one_chip, kernel, direction):
    fn, shapes, n_diff, fwd_names, bwd_names = _cases()[kernel]
    want = set(fwd_names)
    if direction == "bwd":
        want |= set(bwd_names)
        f = fn
        fn = jax.grad(lambda *a: jnp.sum(f(*a) ** 2),
                      argnums=tuple(range(n_diff)))
    got = set(_compiled_kernels(fn, shapes, one_chip))
    assert want <= got, f"compiled program lacks {sorted(want - got)}: {got}"


def test_selection_splits_launches_to_fit_smem(one_chip):
    """Batch 16 has 16·8·480·4 selected-block ids — more than one launch's
    SMEM holds, so the wrapper splits the batch into several launches."""
    fn, shapes, *_ = _cases()["selection"]
    big = [((16,) + s[1:], dt) for s, dt in shapes]
    assert _compiled_kernels(fn, big, one_chip).count("bsa_selection_fwd") == 2


# (batch, points, schedule) of the selection kernel's two schedules at the
# shapenet-bsa widths: BSA training (batch 8) and serve-batch's packed axis
# (8 clouds padded to 3840 points, one row) keep the (b, h) K/V slab in VMEM;
# a 65,536-point cloud's slab overflows the budget and takes the blocked grid
_SCHEDULES = {"train": (8, N, "resident"), "serve-batch": (1, 8 * N, "resident"),
              "large": (1, 65536, "blocked")}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("cell", list(_SCHEDULES))
def test_selection_schedule_compiles_for_v5e(one_chip, cell, direction):
    """Each selection launch compiles on the schedule its shape picks, under
    that schedule's scope inside the kernel's own name."""
    batch, n, schedule = _SCHEDULES[cell]
    fn, _, n_diff, fwd_names, bwd_names = _cases()["selection"]
    sel = (batch, n // GROUP, H, TOP_K)
    shapes = [((batch, n, H, D), jnp.float32)] * 3 + [
        (sel, jnp.int32), (sel, jnp.bool_), ((batch, n), jnp.bool_)]
    want = set(fwd_names)
    if direction == "bwd":
        want |= set(bwd_names)
        f = fn
        fn = jax.grad(lambda *a: jnp.sum(f(*a) ** 2),
                      argnums=tuple(range(n_diff)))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    launches = {line.split("=")[0].strip().lstrip("%"): line
                for line in hlo.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line}
    names = {name.rsplit(".", 1)[0] for name in launches}
    assert want <= names, f"compiled program lacks {sorted(want - names)}"
    assert all(f"/{schedule}/" in line for name, line in launches.items()
               if name.startswith("bsa_selection_")), sorted(launches)


def test_paged_gather_compiles_for_v5e(one_chip):
    fn = lambda pool, rows: ops.paged_gather(pool, rows, interpret=False)
    got = _compiled_kernels(fn, [((4097 * 16, 2, 128), jnp.bfloat16),
                                 ((8, 64), jnp.int32)], one_chip)
    assert got == ["bsa_paged_gather"]


def test_bsa_scopes_reach_the_v5e_kernels(one_chip, monkeypatch):
    """``bsa_attention`` on the pallas backend, compiled for the chip: the
    selection kernel's launch carries the ``bsa/selection/attend`` scope in
    its ``op_name`` and keeps its kernel name (a trace reduction keys on
    both)."""
    from repro.core import bsa_attention, bsa_init
    from repro.core.config import BSAConfig

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cfg = BSAConfig(backend="pallas")                    # PAPER_BSA
    params = jax.eval_shape(lambda: bsa_init(
        jax.random.PRNGKey(0), cfg, n_heads=H, n_kv_heads=H, head_dim=D,
        d_model=H * D))
    spec = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    args = (jax.tree.map(lambda x: spec(x.shape, x.dtype), params),
            *[spec((1, N, H, D))] * 3, spec((1, N), jnp.bool_))
    fn = lambda p, q, k, v, m: bsa_attention(p, q, k, v, cfg=cfg, mask=m)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    launches = [line for line in hlo.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line]
    selection = [line for line in launches
                 if line.split("=")[0].strip().lstrip("%").startswith(
                     "bsa_selection_fwd.")]
    assert selection, [line.split("=")[0] for line in launches]
    assert all("/bsa/selection/attend/" in line for line in selection)
