"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

For deployments deeper than 2 pods the layer stack splits into S stages;
microbatches stream through with ``jax.lax.ppermute`` handoffs inside
``shard_map``.  T = n_micro + S − 1 ticks; stage s computes microbatch
m = t − s when 0 ≤ m < n_micro (the usual fill/drain bubble, fraction
(S−1)/T).  Stage weights live only on their stage's devices.

This module is self-contained (the production dry-run mesh uses DP×TP×SP —
BSA workloads are attention- not depth-bound; see DESIGN §4) and is
unit-tested for exactness against the sequential reference on a 4-way mesh.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x_micro, *, mesh, axis_name="stage"):
    """Run a pipelined stack.

    stage_fn(params_s, x) -> y   (same shape), applied S times in sequence;
    stage_params: pytree with leading STAGE dim S on every leaf;
    x_micro: (n_micro, B, ...) microbatches.
    Returns (n_micro, B, ...) outputs, exactly stage_{S-1}∘…∘stage_0.
    """
    S = mesh.shape[axis_name]
    n_micro = x_micro.shape[0]
    T = n_micro + S - 1

    def per_stage(params, xs):
        # params: this stage's slice (leading dim 1) ; xs: all microbatches
        params = jax.tree.map(lambda t: t[0], params)
        sid = jax.lax.axis_index(axis_name)
        buf = jnp.zeros_like(xs[0])                  # inter-stage register
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            m = t - sid                               # microbatch index at stage
            active = (m >= 0) & (m < n_micro)
            # stage 0 reads fresh input; others read the handoff register
            x_in = jnp.where(sid == 0,
                             xs[jnp.clip(m, 0, n_micro - 1)], buf)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, buf)
            # last stage writes output
            outs = jnp.where(
                (sid == S - 1) & active,
                outs.at[jnp.clip(m, 0, n_micro - 1)].set(y), outs)
            # hand off to next stage
            buf_next = jax.lax.ppermute(
                y, axis_name, [(i, (i + 1) % S) for i in range(S)])
            return buf_next, outs

        buf, outs = jax.lax.fori_loop(0, T, tick, (buf, outs))
        # outputs live on the last stage; psum broadcasts them to all stages
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis_name)
        return outs

    fn = shard_map(per_stage, mesh=mesh,
                   in_specs=(P(axis_name), P()),
                   out_specs=P(), check_vma=False)
    return fn(stage_params, x_micro)
