"""Shared Pallas kernel utilities.

TPU is the TARGET; on this CPU container kernels run under interpret mode
(``interpret=True`` executes the kernel body in Python for correctness).
``should_interpret()`` auto-detects; set REPRO_PALLAS_INTERPRET=0/1 to force.

All four kernels are differentiable via ``jax.custom_vjp``: the forward
kernels emit a per-query-row logsumexp residual (``lse = m + log l``) and the
backward kernels recompute the attention probabilities per tile as
``p = exp(s − lse)`` (FlashAttention-style recomputation — O(N) residual
memory instead of materialising p).  ``lse_finalize`` / ``p_from_lse`` keep
the two sides of that contract in one place.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.numerics import NEG_INF  # noqa: F401 — shared constant, re-exported
                                    # for the kernel modules

# Sentinel logsumexp for query rows with NO valid key (fully-masked ball /
# all-invalid selection group): exp(s − LSE_EMPTY) underflows to exactly 0
# for any finite logit s, so backward recomputation yields p ≡ 0 for the row.
LSE_EMPTY = 1e30


def fp8_enabled() -> bool:
    """Opt-in fp8 QK^T experiment (REPRO_FP8=1).  Off by default."""
    return os.environ.get("REPRO_FP8", "") not in ("", "0", "false", "False")


def resolve_compute_dtype(dtype) -> str:
    """Input dtype → canonical MATMUL-OPERAND dtype name for the kernels.

    The kernel-level precision contract (docs/architecture.md):

      * fp32/fp64 inputs compute in fp32 — bit-identical to the historical
        force-upcast behaviour;
      * sub-fp32 inputs (bf16/fp16) keep their storage dtype as the matmul
        operand dtype — Q/K/V tiles stay bf16 through QK^T and PV — while
        every ``dot_general`` accumulates fp32 (``preferred_element_type``)
        and all softmax statistics / lse / scratch stay fp32;
      * with REPRO_FP8=1, sub-fp32 inputs use float8_e4m3fn for the QK^T
        OPERANDS only (the experiment); non-QK matmuls stay ≥ 16-bit via
        ``mma_dtype``.

    Returns a canonical dtype NAME (hashable, cache-key friendly).
    """
    d = jnp.dtype(dtype)
    if d.itemsize >= 4:
        return "float32"
    if fp8_enabled() and hasattr(jnp, "float8_e4m3fn"):
        return "float8_e4m3fn"
    return d.name


def mma_dtype(compute: str) -> str:
    """Operand dtype for the non-QK^T matmuls (PV, dP, dQ, dK, dV).

    fp8 is a QK^T-only experiment: everything else never drops below
    16 bits, so gradients and the PV contraction keep bf16 operands."""
    return "bfloat16" if jnp.dtype(compute).itemsize == 1 else compute


def should_interpret() -> bool:
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"


def lse_finalize(m_safe, l):
    """Per-row logsumexp residual from running max/sum.  (rows, 1) fp32.

    ``l ≥ 1`` whenever any key is valid (the max term contributes exp(0)=1),
    so ``lse ≥ m ≥ s`` and backward ``exp(s − lse) ≤ 1`` never overflows.
    """
    return jnp.where(l > 0.0, m_safe + jnp.log(jnp.maximum(l, 1e-30)), LSE_EMPTY)


def p_from_lse(s, lse):
    """Recompute normalised attention probabilities from logits + residual."""
    p = jnp.exp(s - lse)
    return jnp.where(s <= NEG_INF / 2, 0.0, p)


def rows_to_column(x):
    """(rep, t) per-query-row statistics → the (rep·t, 1) column of the
    fused rep-major row tile (row r·t + i ↔ x[r, i]).

    Mosaic relayouts one lane-major row into a sublane column, but not a
    whole (rep, t) block in one reshape, so rep > 1 goes row by row."""
    rep, t = x.shape
    return jnp.concatenate([x[r:r + 1].reshape(t, 1) for r in range(rep)],
                           axis=0)


def interpret_batch_map(fn, *args):
    """Sequential ``lax.map`` of a kernel call over leading-dim slices.

    INTERPRET-MODE ONLY.  The Pallas interpreter's per-grid-cell cost grows
    with the TOTAL operand size, so a batched grid costs O(B²) on CPU —
    mapping per-sample slices keeps it linear while staying one jitted
    computation (and differentiable: scan-of-custom_vjp).  Compiled TPU runs
    never take this path; there the batched grid is the whole point.
    """
    return jax.lax.map(lambda t: fn(*[a[None] for a in t])[0], args)
