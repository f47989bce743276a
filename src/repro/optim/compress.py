"""Gradient compression for the slow cross-pod (DCI) reduction.

int8 block-quantized all-gather-sum with ERROR FEEDBACK: instead of a bf16
ring all-reduce over the ``pod`` axis (2× bytes on the wire), each pod
quantizes its gradient shard to int8 (per-block scale), all-gathers the
int8 payload (¼ the bytes of bf16, and 1× instead of 2×), and sums locally.
The quantization residual is carried in the optimizer state and added to the
next step's gradient — standard EF-SGD, keeps convergence unbiased in the
long run.  Net wire traffic: 8× less than bf16 all-reduce.

Exposed as a ``shard_map``-based transform of per-pod gradients; unit-tested
against exact psum (quantization error bound + error-feedback convergence).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

BLOCK = 256


def _quantize(g: jnp.ndarray):
    """per-block int8 quantization; returns (q, scale, residual)."""
    flat = g.reshape(-1)
    pad = (-flat.size) % BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    deq = (q.astype(jnp.float32) * scale).reshape(flat.shape)[:g.size].reshape(g.shape)
    return q, scale.astype(jnp.float32), g - deq


def _dequantize(q, scale, shape):
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape)


def compressed_psum(g: jnp.ndarray, err: jnp.ndarray, axis_name: str):
    """Inside shard_map: error-feedback int8 'psum' over ``axis_name``.

    Returns (summed gradient ≈ psum(g), new residual)."""
    g = g + err                                  # error feedback
    q, scale, residual = _quantize(g)
    q_all = jax.lax.all_gather(q, axis_name)          # int8 on the wire
    s_all = jax.lax.all_gather(scale, axis_name)
    n = q_all.shape[0]
    total = jnp.zeros(g.shape, jnp.float32)
    for i in range(n):                                # static unroll (n = pods)
        total = total + _dequantize(q_all[i], s_all[i], g.shape)
    return total.astype(g.dtype), residual


def make_compressed_grad_fn(loss_fn, mesh, *, axis_name: str = "pod"):
    """Wrap a loss into a shard_map'd per-pod grad + compressed cross-pod
    reduction.  Gradients w.r.t. REPLICATED params; batch sharded over pod."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def per_pod(params, batch, err):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        out = jax.tree.map(
            lambda g, e: compressed_psum(g, e, axis_name), grads, err)
        grads = jax.tree.map(lambda t: t[0] / mesh.shape[axis_name], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        new_err = jax.tree.map(lambda t: t[1], out,
                               is_leaf=lambda t: isinstance(t, tuple))
        loss = jax.lax.pmean(loss, axis_name)
        return loss, grads, new_err

    return shard_map(
        per_pod, mesh=mesh,
        in_specs=(P(), P(axis_name), P()),
        out_specs=(P(), P(), P()),
        check_vma=False)
