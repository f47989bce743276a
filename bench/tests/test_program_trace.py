"""bench/program_trace.py: the program's spans, scopes and compiles on hand-
made events, and on the recorded traces of one v5e serve-single window."""

from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from bench import program_trace as pt
from bench import trace

HERE = Path(__file__).resolve().parent
OLD = HERE / "serve-single-events.json.gz"
SCOPED = HERE / "serve-single-scoped-events.json.gz"
MS = 1e6                                                   # ns


def hand_made():
    """One request: predict > batch > (balltree, pack, forward, fetch,
    unpack), a compile in the window, and ops under three scopes."""
    return {
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.request", 10 * MS, 80 * MS],
                 ["repro.engine.predict", 12 * MS, 76 * MS, {"clouds": 1, "points": 5}],
                 ["repro.engine.batch", 12 * MS, 76 * MS, {"clouds": 1}],
                 ["repro.engine.balltree", 12 * MS, 8 * MS, {}],
                 ["repro.engine.pack", 20 * MS, 4 * MS, {}],
                 ["repro.engine.forward", 24 * MS, 6 * MS, {}],
                 ["backend_compile_and_load", 25 * MS, 4 * MS, {}],
                 ["repro.engine.fetch", 30 * MS, 50 * MS, {}],
                 ["repro.engine.unpack", 80 * MS, 7 * MS, {}]],
        "device": {"/device:TPU:0": [
            ["%bsa_selection_fwd.3 = f32[..] custom-call(..)", 30 * MS, 20 * MS,
             "bsa/selection/attend"],
            ["%sort.8 = f32[..] sort(..)", 45 * MS, 10 * MS, "bsa/selection/topk"],
            ["%fusion.1 = f32[..] fusion(..)", 60 * MS, 15 * MS, "ffn"],
            ["%copy.2 = f32[..] copy(..)", 75 * MS, 1 * MS, ""],
            ["%while.4 = (..) while(..)", 30 * MS, 50 * MS, ""]]}}


def test_hand_made_spans_scopes_gaps_compiles():
    r = pt.reduce(hand_made())
    s = r["spans"]
    assert s["repro.engine.predict"] == {"count": 1, "seconds": pytest.approx(0.076),
                                         "self_seconds": pytest.approx(0.0)}
    # the batch's children cover all but 1 ms (87..88) of it
    assert s["repro.engine.batch"]["self_seconds"] == pytest.approx(0.001)
    assert s["repro.engine.fetch"]["self_seconds"] == pytest.approx(0.05)
    # a compile span is no child: the forward's self time is its duration
    assert s["repro.engine.forward"]["self_seconds"] == pytest.approx(0.006)
    assert r["scopes"] == {"": pytest.approx(0.001),
                           "bsa/selection/attend": pytest.approx(0.02),
                           "bsa/selection/topk": pytest.approx(0.01),
                           "ffn": pytest.approx(0.015)}
    assert r["busy_s"] == pytest.approx(0.041)            # the loop is no op
    assert r["compiles"] == {"count": 1, "seconds": pytest.approx(0.004)}
    assert pt.reduce(hand_made(), top=20)["idle_gaps"] == [
        [name, pytest.approx(ms * 1e-3)] for name, ms in [
            ("outside benchmark spans", 10), ("outside benchmark spans", 10),
            ("repro.engine.balltree", 8), ("repro.engine.unpack", 7),
            ("repro.engine.forward", 6), ("repro.engine.fetch", 5),
            ("repro.engine.pack", 4), ("repro.engine.fetch", 4),
            ("bench.request", 2), ("bench.request", 2),
            ("repro.engine.batch", 1)]]
    # bench.trace names every gap in the request after the request
    old = dict(trace.reduce({"host": [h[:3] for h in hand_made()["host"]
                                      if h[0].startswith("bench.")],
                             "device": {p: [o[:3] for o in ops] for p, ops in
                                        hand_made()["device"].items()}})["idle_gaps"])
    assert set(old) == {"bench.request", "outside benchmark spans"}
    rec = {"trace": r}
    assert pt.selection_share(rec) == pytest.approx(30.0)
    assert pt.engine_prep_ms(rec) == pytest.approx(8 + 4 + 7)


def test_innermost_span_past_many_finished_siblings():
    """A gap after twelve finished spans still belongs to the span around
    them all."""
    host = [["bench.window", 0, 1000], ["repro.engine.predict", 0, 1000, {}]]
    host += [["repro.engine.batch", 10 * i, 5, {}] for i in range(12)]
    ev = {"host": host, "device": {"/device:TPU:0": [["a", 0, 1, "bsa"]]}}
    gaps = pt.reduce(ev)["idle_gaps"]
    assert gaps[0] == ["repro.engine.predict", pytest.approx(885e-9)]


@pytest.mark.parametrize("op_name,path", [
    ("jit(_forward)/while/body/closed_call/bsa/selection/topk/top_k",
     "bsa/selection/topk"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/bsa/selection/score/dot_general", "bsa/selection/score"),
    ("jit(step)/jvp(loss)/mul", "loss"),
    ("jit(step)/transpose(jvp(loss))/mul", "loss"),
    ("jit(step)/clip/jit(norm)/sqrt", "clip"),
    ("jit(g)/bsa/selection/attend/jit(selection_attention_kernel_call)/"
     "bsa_selection_fwd/pallas_call", "bsa/selection/attend"),
    ("jit(step)/ffn", ""),
    ("", ""),
    # as the v5e trace gives them: a fusion's fused names, ``;`` apart, and
    # a called computation's names joined to its caller's
    ("jit(<lambda>)/while/body/closed_call/checkpoint/bsa/compression/reshape;"
     "checkpoint/bsa/compression/broadcast_in_dim;checkpoint/attn_proj/reshape:",
     "bsa/compression"),
    ("jit(<lambda>)/while/body/closed_call/checkpoint/bsa/jit(searchsorted)/"
     "checkpoint/bsa/compression/jit(searchsorted)/vmap()/while:", "bsa/compression"),
    ("jit(<lambda>)/while/body/closed_call/checkpoint/bsa/selection/topk/top_k:",
     "bsa/selection/topk"),
])
def test_scope_path(op_name, path):
    assert pt.scope_path(op_name) == path


def test_parent_trace_reduces_as_before_and_reads_none():
    """On the parent program's trace (no repro.* span, no scope) the result
    is bench.trace's, key for key, and the new readings are None."""
    ev = json.loads(gzip.decompress(OLD.read_bytes()))
    old, new = trace.reduce(ev), pt.reduce(ev)
    assert json.dumps({k: new[k] for k in old}) == json.dumps(old)
    assert new["spans"] == {} and set(new["scopes"]) == {""}
    assert new["compiles"] == {"count": 0, "seconds": 0.0}
    for reduced in (old, new):
        assert pt.selection_share({"trace": reduced}) is None
        assert pt.engine_prep_ms({"trace": reduced}) is None


def test_op_names_from_the_trace_viewer_export(tmp_path):
    """The export beside the .xplane.pb names each op's HLO op_name (its
    ``tf_op``), keyed as the device op events are."""
    ev = [{"ph": "M", "pid": 3, "name": "process_name", "args": {"name": "/device:TPU:0"}},
          {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name", "args": {"name": "XLA Ops"}},
          {"ph": "M", "pid": 3, "tid": 4, "name": "thread_name",
           "args": {"name": "Async XLA Ops"}},
          {"ph": "X", "pid": 3, "tid": 3, "name": "sort.11", "ts": 1.0, "dur": 2.0,
           "args": {"device_offset_ps": "1000000", "tf_op": "jit(f)/bsa/selection/topk/top_k:"}},
          {"ph": "X", "pid": 3, "tid": 4, "name": "copy-start", "ts": 1.0, "dur": 1.0,
           "args": {"device_offset_ps": "1000000"}}]
    (tmp_path / "host.trace.json.gz").write_bytes(
        gzip.compress(json.dumps({"traceEvents": ev}).encode()))
    names = pt.op_names(tmp_path / "host.xplane.pb")
    assert names == {("/device:TPU:0", "XLA Ops", "1000000"):
                     "jit(f)/bsa/selection/topk/top_k:"}
    assert pt.op_names(tmp_path / "sub" / "none.xplane.pb") == {}


def test_recorded_scoped_trace():
    """A v5e serve-single window of the program with its spans and scopes:
    its reduction, the old keys unchanged, no gap left to a bare request,
    95% of busy time scoped and every selection launch under selection."""
    ev = json.loads(gzip.decompress(SCOPED.read_bytes()))
    r = pt.reduce(ev)
    want = json.loads((HERE / "serve-single-scoped-reduced.json").read_text())
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["scopes"] == pytest.approx(want["scopes"])
    assert {k: v["count"] for k, v in r["spans"].items()} == want["span_counts"]
    assert r["compiles"] == want["compiles"]
    assert pt.selection_share({"trace": r}) == pytest.approx(want["selection_share"])
    assert pt.engine_prep_ms({"trace": r}) == pytest.approx(want["engine_prep_ms"])
    # the old reduction's keys keep their values; no gap is a bare request
    old = trace.reduce({"host": [h[:3] for h in ev["host"] if h[0].startswith("bench.")],
                        "device": {p: [o[:3] for o in ops]
                                   for p, ops in ev["device"].items()}})
    assert json.dumps({k: r[k] for k in old if k != "idle_gaps"}) == json.dumps(
        {k: v for k, v in old.items() if k != "idle_gaps"})
    assert "bench.request" not in {name for name, _ in r["idle_gaps"]}
    # scoped ops cover 95% of busy time; every selection launch is selection
    assert r["scopes"].get("", 0.0) < 0.05 * r["busy_s"]
    ops = [o for plane in ev["device"].values() for o in plane]
    sel = [o for o in ops if trace.op_name(o[0]).startswith("bsa_selection")]
    assert sel and all(o[3].startswith(pt.SELECTION + "/") for o in sel)
    # one cloud of 3586 points per request
    predict = [h for h in ev["host"] if h[0] == "repro.engine.predict"]
    assert predict and all(h[3] == {"clouds": 1, "points": 3586} for h in predict)
