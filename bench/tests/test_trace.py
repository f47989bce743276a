"""The trace reduction, on a hand-made event list and on a trimmed trace of
``shapenet-bsa.serve-single`` recorded on a TPU v5e."""

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace

FIXTURE = Path(__file__).resolve().parent / "serve-single-events.json.gz"


def test_hand_made_events():
    ms = 1e6                                               # ns
    ev = {"host": [["bench.window", 0, 100 * ms],
                   ["bench.request_gen", 0, 10 * ms],
                   ["bench.request", 10 * ms, 90 * ms]],
          "device": {"/device:TPU:0": [
              ["bsa_ball_fwd.3", 20 * ms, 10 * ms],
              ["fusion.1", 25 * ms, 10 * ms],                # overlaps the kernel
              ["bsa_ball_fwd.7", 60 * ms, 20 * ms],
              ["copy.2", 95 * ms, 10 * ms]]}}                # runs past the window
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((15 + 20 + 5) * 1e-3)
    assert r["kernels"] == {"bsa_ball_fwd": {"seconds": pytest.approx(0.03),
                                             "count": 2}}
    assert r["idle_gaps"][0] == ["bench.request", pytest.approx(0.025)]
    assert ["bench.request_gen", pytest.approx(0.01)] in r["idle_gaps"]
    assert r["requests"] == [[pytest.approx(0.09), pytest.approx(0.04)]]
    assert r["device_ops"][0] == ["bsa_ball_fwd", pytest.approx(0.03)]


def test_two_devices_average():
    ev = {"host": [["bench.window", 0, 100]],
          "device": {"/device:TPU:0": [["a", 0, 50]],
                     "/device:TPU:1": [["a", 0, 100]]}}
    r = trace.reduce(ev)
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(75e-9)


def brute_force_busy(ev, step_ns=100):
    """Busy time by marking every 100 ns an operation (not a loop around
    operations) covers."""
    (w0, dur), = [(s, d) for n, s, d in ev["host"] if n == "bench.window"]
    busy = []
    for ops in ev["device"].values():
        grid = np.zeros(int(dur // step_ns) + 1, bool)
        for name, s, d in ops:
            if name.lstrip("%").startswith(trace.CONTAINERS):
                continue
            a, b = max(s, w0), min(s + d, w0 + dur)
            if b > a:
                grid[int((a - w0) // step_ns):int((b - w0) // step_ns)] = True
        busy.append(grid.sum() * step_ns * 1e-9)
    return float(np.mean(busy)), sum(len(ops) for ops in ev["device"].values())


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded trace")
def test_recorded_trace():
    ev = json.loads(gzip.decompress(FIXTURE.read_bytes()))
    r = trace.reduce(ev)
    busy, n_ops = brute_force_busy(ev)
    assert r["busy_s"] == pytest.approx(busy, abs=2e-7 * n_ops)
    assert 0 < r["busy_s"] < r["window_s"]
    want = json.loads((FIXTURE.parent / "serve-single-reduced.json").read_text())
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert {k: v["count"] for k, v in r["kernels"].items()} == want["kernel_counts"]
    assert len(r["requests"]) == want["requests"]
    # one launch of each forward kernel per layer (18) and request
    assert set(want["kernel_counts"].values()) == {18 * want["requests"]}
    assert all(0 < busy < span for span, busy in r["requests"])
