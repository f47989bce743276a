"""Arithmetic shared by the per-layer metric readers (``bench/metrics``).

``rec`` holds the traced run: ``window`` (the window's record), ``trace``
(``bench.trace.reduce``), ``work`` (required operations and bytes of the
window's work, ``bench.flops``), ``peak`` (the device's row of
``bench/peaks.json``) and ``cell``.
"""

from __future__ import annotations


def kernel_roofline(rec: dict) -> float | None:
    """% of the roofline the ``bsa_*`` kernels reach together: the sum over
    kernels of the least time the chip could take for the work they must do
    (the larger of operations over peak rate and bytes over memory
    bandwidth) over the sum of their device time."""
    kernels, work, peak = rec["trace"]["kernels"], rec["work"]["kernels"], rec["peak"]
    if not kernels:
        return None
    uncounted = sorted(set(kernels) - set(work))
    if uncounted:
        raise ValueError(f"kernels ran whose work is not counted: {uncounted}")
    least = sum(max(work[k][0] / peak["bf16_flops_per_s"],
                    work[k][1] / peak["hbm_bytes_per_s"]) for k in kernels)
    return 100.0 * least / sum(k["seconds"] for k in kernels.values())


def mfu(rec: dict) -> float:
    """% of the chips' bf16 peak: required model operations of the window's
    work over the window's time."""
    t = rec["trace"]
    return 100.0 * rec["work"]["model_flops"] / (
        t["window_s"] * t["devices"] * rec["peak"]["bf16_flops_per_s"])


def idle_share(rec: dict) -> float:
    """% of the window in which no operation ran on the device."""
    t = rec["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def host_ms_per_request(rec: dict) -> float | None:
    """Mean over requests of the request span minus the device-busy time
    inside it, in ms."""
    reqs = rec["trace"]["requests"]
    if not reqs:
        return None
    return 1e3 * sum(span - busy for span, busy in reqs) / len(reqs)
