"""``correct`` at a size the CPU holds: the harness's run with the program
as it is comes out correct; the bfloat16 controls (the program's own
bfloat16 path, and the reference in bfloat16 in the program's place) and
every fault a cell can have, planted under the timed path, come out not
correct.  The chip check is skipped: the run goes through
``bench.run.run_cell`` on the CPU, the kernels in interpret mode.

The limits here are this size's own, set from its readings on the CPU,
where the program's matmuls and the reference's are float32 (see
``tiny.config``).  The cells' limits on the chip are in ``bench/limits``.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from bench import compare, control, program
from bench import run as bench_run
from bench.tests import tiny

LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-4, "update_gap": 2e-4,
          "selection_gap": 1e-4, "prediction_gap": 1e-4,
          "prediction_rms_gap": 1e-4, "selection_pass_gap": 1e-5}
TRAIN = [("shapenet-bsa", "train-b8"), ("shapenet-full", "train-b8")]
SERVE = [("shapenet-bsa", "serve-single"), ("shapenet-bsa", "serve-batch16")]
SEED = 2**31 + 101


def tiny_limits_of(name: str) -> dict:
    keys = (["loss_gap", "grad_gap", "update_gap"] if name.endswith("train-b8")
            else ["prediction_gap", "prediction_rms_gap"])
    if ".shapenet-bsa." in name:
        keys += ["selection_gap", "selection_pass_gap"]
    return {k: LIMITS[k] for k in keys}


@pytest.fixture(autouse=True)
def tiny_limits(monkeypatch):
    monkeypatch.setattr(compare, "load_limits", tiny_limits_of)


def run(cell):
    return bench_run.run_cell(cell, SEED, 0.5, False, None, jax.devices()[0],
                              time.perf_counter())


def step_keeping_state(real):
    def make(api, **kw):
        step = real(api, **kw)

        def broken(params, opt_state, batch):
            return (params, opt_state) + step(params, opt_state, batch)[2:]
        return broken
    return make


def step_on_half_batch(real):
    def make(api, **kw):
        half = lambda p, b: api.loss(p, {k: v[:v.shape[0] // 2] for k, v in b.items()})
        return real(dataclasses.replace(api, loss=half), **kw)
    return make


def answer_altered(real):
    def make(cfg):
        api = real(cfg)
        fwd = lambda p, b: api.forward(p, b).at[0, 3, 0].add(1.0)
        return dataclasses.replace(api, forward=fwd)
    return make


def selection_pass_altered(real):
    """A selection pass that does not follow the timed path: its ids are
    then not the ones the timed path used."""
    def make(cfg):
        api = real(cfg)

        def fwd_sel(p, b):
            pred, sel = api.forward_selection(p, b)
            return pred.at[0, 3, 0].add(1.0), sel
        return dataclasses.replace(api, forward_selection=fwd_sel)
    return make


@pytest.mark.parametrize("names", TRAIN + SERVE, ids="-".join)
def test_program_as_it_is_is_correct(names):
    out = run(tiny.cell(*names))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("names", TRAIN + SERVE, ids="-".join)
def test_control_is_not_correct(names):
    cell = tiny.cell(*names)
    seen = list(control.readings(cell, [], [SEED], 0.5))
    assert [r["kind"] for r in seen] == list(
        control.PLANTS[:3 if names in TRAIN else 2])
    for r in seen:
        assert "error" not in r, r
        ok, checks = compare.judge(r["numbers"], compare.load_limits(cell["name"]))
        assert not ok, (r["kind"], checks)


@pytest.mark.parametrize("fault", [step_keeping_state, step_on_half_batch],
                         ids=["state-unchanged", "half-batch"])
@pytest.mark.parametrize("names", TRAIN, ids="-".join)
def test_training_faults_are_not_correct(names, fault, monkeypatch):
    import repro.runtime.trainer as trainer
    monkeypatch.setattr(trainer, "make_train_step", fault(trainer.make_train_step))
    out = run(tiny.cell(*names))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("names", SERVE, ids="-".join)
def test_altered_answer_is_not_correct(names, monkeypatch):
    monkeypatch.setattr(program, "model_api", answer_altered(program.model_api))
    out = run(tiny.cell(*names))
    assert not out["correct"], out["checks"]
    assert out["checks"]["prediction_gap"]["value"] > 1e-2


def test_the_reference_follows_the_program_without_it():
    """The reference's weights come from the seed alone and equal the
    program's; it imports nothing of the program."""
    import ast
    from pathlib import Path

    from bench.configs import pointcloud_ref as ref
    src = Path(ref.__file__).read_text()
    imported = {n.names[0].name.split(".")[0] if isinstance(n, ast.Import)
                else (n.module or "").split(".")[0]
                for n in ast.walk(ast.parse(src))
                if isinstance(n, (ast.Import, ast.ImportFrom))}
    assert imported <= {"__future__", "math", "jax", "numpy"}, imported
    cfg = tiny.config()
    want = jax.tree_util.tree_flatten_with_path(ref.init(SEED, cfg))[0]
    got = program.model_api(cfg).init(jax.random.PRNGKey(SEED))
    got = dict((jax.tree_util.keystr(p), v)
               for p, v in jax.tree_util.tree_flatten_with_path(got)[0])
    assert set(got) == {jax.tree_util.keystr(p) for p, _ in want}
    for p, v in want:
        assert jnp.array_equal(got[jax.tree_util.keystr(p)], v)


@pytest.mark.parametrize("names", [n for n in TRAIN + SERVE if n[0] == "shapenet-bsa"],
                         ids="-".join)
def test_selection_pass_off_the_timed_path_is_not_correct(names, monkeypatch):
    monkeypatch.setattr(program, "model_api", selection_pass_altered(program.model_api))
    out = run(tiny.cell(*names))
    assert not out["correct"], out["checks"]
    assert out["checks"]["selection_pass_gap"]["value"] > 1e-3


def test_reference_rounds_operands_and_cotangents_to_bfloat16():
    """The stated precision's one bfloat16 pass: operands and the gradient
    flowing back into each matmul rounded to bfloat16, ties to even."""
    from bench.configs import pointcloud_ref as ref

    x = jax.random.normal(jax.random.PRNGKey(0), (4096,), jnp.float32) * 1e3
    ties = jnp.array([1 + 2**-8, 1 + 3 * 2**-8, -(1 + 2**-8)], jnp.float32)
    for v in (x, ties):
        want = v.astype(jnp.bfloat16).astype(jnp.float32)
        assert jnp.array_equal(jax.jit(ref.round_bf16)(v), want)
    a = jax.random.normal(jax.random.PRNGKey(1), (8, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (16, 4), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(3), (8, 4), jnp.float32)
    mm = lambda a, w: ref._mm("mi,io->mo", a, w, jnp.dtype(jnp.bfloat16))
    r = ref.round_bf16
    with jax.default_matmul_precision("highest"):
        y, vjp = jax.vjp(mm, a, w)
        da, dw = vjp(g)
        assert jnp.allclose(y, r(a) @ r(w), rtol=1e-6)
        assert jnp.allclose(da, r(g) @ r(w).T, rtol=1e-6)
        assert jnp.allclose(dw, r(a).T @ r(g), rtol=1e-6)
        assert not jnp.allclose(y, a @ w, rtol=1e-6)
