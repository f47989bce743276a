"""The program's own trace: host spans of ``GeometryEngine.predict`` and
``Trainer.fit`` (``repro.*``, read back from a CPU profile with
``ProfileData``) and the ``jax.named_scope`` paths of the BSA branches in
the compiled program's HLO ``op_name`` metadata.  The scopes must leave
instruction names alone (a trace reduction keys on them); the v5e kernels'
names are checked in ``test_tpu_compile.py``."""

import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import bsa_attention, bsa_attention_varlen, bsa_init
from repro.core.balltree import bucket_length
from repro.core.config import BSAConfig
from repro.models.api import model_api
from repro.runtime import Trainer, TrainerConfig
from repro.serving import GeometryEngine

ENGINE_CHILDREN = ["repro.engine.balltree", "repro.engine.pack",
                   "repro.engine.forward", "repro.engine.fetch",
                   "repro.engine.unpack"]


@pytest.fixture(autouse=True)
def _no_env_override(monkeypatch):
    monkeypatch.delenv("REPRO_ATTENTION_BACKEND", raising=False)


def _tiny_api():
    mcfg = get_config("shapenet-bsa").scaled(
        n_layers=2, d_model=32, n_heads=2, head_dim=16, n_kv_heads=2, d_ff=64)
    mcfg = mcfg.scaled(bsa=dataclasses.replace(mcfg.bsa, ball_size=16,
                                               local_window=16))
    return model_api(mcfg)


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; its ``repro.*`` host spans as
    (name, start, end, args), in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _parent(span, spans):
    """The innermost other span around ``span``."""
    around = [s for s in spans if s is not span
              and s[1] <= span[1] and span[2] <= s[2]]
    return max(around, key=lambda s: s[1])[0] if around else None


def _clouds(sizes, in_dim):
    rng = np.random.default_rng(5)
    return [(rng.standard_normal((n, 3)).astype(np.float32),
             rng.standard_normal((n, in_dim)).astype(np.float32)) for n in sizes]


@pytest.mark.parametrize("layout", ["packed", "padded"])
def test_engine_predict_spans(tmp_path, layout):
    api = _tiny_api()
    eng = GeometryEngine(api, api.init(jax.random.PRNGKey(0)), batch_slots=2,
                         layout=layout)
    sizes = (20, 45, 33)                     # batches of 2 clouds and 1
    clouds = _clouds(sizes, api.mcfg.in_dim)
    eng.predict(clouds)                      # compile outside the trace
    spans = _traced(tmp_path, lambda: eng.predict(clouds))

    predict, = [s for s in spans if s[0] == "repro.engine.predict"]
    assert predict[3] == {"clouds": 3, "points": sum(sizes)}
    batches = [s for s in spans if s[0] == "repro.engine.batch"]
    assert [(b[3]["clouds"], b[3]["points"]) for b in batches] == [(2, 65), (1, 33)]
    assert all(b[3]["layout"] == layout for b in batches)
    # the packed capacity, or the per-slot bucket of the longest cloud
    ball = lambda n: bucket_length(n, 16, geometric=False)
    want = ([bucket_length(ball(20) + ball(45), 16), bucket_length(ball(33), 16)]
            if layout == "packed" else [bucket_length(45, 16), bucket_length(33, 16)])
    assert [b[3]["length"] for b in batches] == want
    assert all(_parent(b, spans) == "repro.engine.predict" for b in batches)
    for b in batches:
        inside = [s for s in spans if _parent(s, spans) == "repro.engine.batch"
                  and b[1] <= s[1] < b[2]]
        assert [s[0] for s in inside] == ENGINE_CHILDREN


def test_trainer_step_spans(tmp_path):
    api = _tiny_api()
    trainer = Trainer(api, TrainerConfig(log_every=2, ckpt_every=2,
                                         ckpt_dir=str(tmp_path / "ckpt")))
    rng = np.random.default_rng(0)
    batches = iter(lambda: api.make_batch(rng, 2, 32), None)
    params, opt = trainer.init_state()
    params, opt = trainer.fit(batches, steps=1, params=params, opt_state=opt,
                              start_step=0)           # compile outside
    spans = _traced(tmp_path / "trace", lambda: trainer.fit(
        batches, steps=3, params=params, opt_state=opt, start_step=1))

    steps = [s for s in spans if s[0] == "repro.trainer.step"]
    assert [s[3]["step_num"] for s in steps] == [1, 2, 3]
    assert all(_parent(s, spans) is None for s in steps)
    children = {s[3]["step_num"]: [c[0] for c in spans
                                   if _parent(c, spans) == "repro.trainer.step"
                                   and s[1] <= c[1] < s[2]] for s in steps}
    # a log on steps divisible by log_every and on the last; a save on
    # checkpoint steps after the first of the call, and on the last
    base = ["repro.trainer.batch", "repro.trainer.dispatch", "repro.trainer.wait"]
    assert children == {1: base,
                        2: base + ["repro.trainer.log", "repro.trainer.checkpoint"],
                        3: base + ["repro.trainer.log", "repro.trainer.checkpoint"]}
    trainer.ckpt.wait()


def _scoped_hlo(varlen: bool):
    """The compiled jnp-backend BSA call's instructions: (name, op_name)."""
    cfg = BSAConfig(ball_size=16, local_window=16, cmp_block=8, top_k=2,
                    group_size=8, backend="jnp")
    H, D, N = 2, 16, 64
    params = bsa_init(jax.random.PRNGKey(0), cfg, n_heads=H, n_kv_heads=H,
                      head_dim=D, d_model=H * D)
    q = jnp.ones((N, H, D)) if varlen else jnp.ones((1, N, H, D))
    if varlen:
        fn = lambda p, q: bsa_attention_varlen(
            p, q, q, q, cfg=cfg, offsets=jnp.array([0, 32, 64], jnp.int32))
    else:
        fn = lambda p, q: bsa_attention(p, q, q, q, cfg=cfg)
    text = jax.jit(fn).lower(params, q).compile().as_text()
    return re.findall(r'^\s*(?:ROOT )?%(\S+) = .*metadata=\{op_name="([^"]*)"',
                      text, re.M)


@pytest.mark.parametrize("varlen", [False, True], ids=["batched", "varlen"])
def test_bsa_scopes_in_op_name_not_in_instruction_names(varlen):
    instrs = _scoped_hlo(varlen)
    topk = [name for name, op in instrs if "/bsa/selection/topk/" in op]
    assert topk, "no op of the compiled program is under bsa/selection/topk"
    for branch in ("ball", "compression", "selection/score", "combine"):
        assert any(f"/bsa/{branch}/" in op for _, op in instrs), branch
    scopes = ("bsa", "ball", "compression", "selection", "score", "topk",
              "attend", "combine")
    for name, _ in instrs:
        assert re.fullmatch(r"[\w.\-]+", name), name
        assert not name.startswith(scopes), name
