"""Shared fixtures of the test suite."""

import pytest


@pytest.fixture
def use_selection_schedule(monkeypatch):
    """``use(schedule, groups_per_step=None)``: run the selection kernel on
    the "resident" or "blocked" schedule (None: what the shape picks), with
    ``groups_per_step`` groups per resident grid step.  The constants are
    read when the kernel call is traced, so its caches are cleared around."""
    from repro.kernels import selection

    def clear():
        selection.selection_attention_kernel_call.clear_cache()
        selection._make_vjp.cache_clear()

    def use(schedule, groups_per_step=None):
        if schedule == "blocked":
            monkeypatch.setattr(selection, "_RESIDENT_VMEM_BYTES", 0)
        if groups_per_step is not None:
            monkeypatch.setattr(selection, "_GROUPS_PER_STEP", groups_per_step)
        clear()

    yield use
    clear()
