"""Ring context parallelism: ``lax.ppermute`` K/V rotation primitives.

The sharded backend's three remaining fallbacks (token-causal flash,
replicated-K/V selection, unsharded packed-varlen) all reduce to the same
missing primitive: attention where the QUERIES stay put and the KEYS/VALUES
travel.  This module provides it in three shapes:

* :func:`ring_flash` — dense flash attention with every operand sequence-
  sharded.  Each of the ``p`` hops attends the resident K/V slab, merges the
  partial result into running online-softmax statistics ``(m, l, acc)``, and
  rotates the slab to the right neighbour (``lax.ppermute``).  Per-shard K/V
  memory is O(L/p); the all-gather of the replicated path never happens.
  A hand-written ``jax.custom_vjp`` keeps the kernels' residual contract:
  the backward saves only ``(out, lse)`` and RECOMPUTES each hop's
  probabilities from the logsumexp while the K/V slabs (and the travelling
  dK/dV accumulators) make one more full revolution — so backward memory is
  O(L/p) too, exactly like the fused Pallas backwards.
* **Causal hop skipping** — with token-causal masking, hop ``h`` on shard
  ``i`` brings the slab of source shard ``(i - h) mod p``, which is entirely
  in shard ``i``'s future whenever ``h > i``.  The static ``(p, p)`` live
  table from :func:`repro.kernels.occupancy.ring_hop_live` (the tile
  liveness math at hop granularity) gates each hop's compute behind
  ``lax.cond`` — the rotation itself still runs on every shard (it is a
  collective), but dead hops issue no matmuls, so the causal ring does
  ``p(p+1)/2`` of ``p²`` hop-computations (~half the work).
* :func:`ring_selection` — the selection branch with K/V *sharded*: top-k
  block indices are re-based to ring-local coordinates each hop
  (``loc = top_idx − src·nb_loc``); a hop attends only the selected blocks
  resident on the current slab, and hops that hold none of a shard's
  selections are skipped at runtime (``lax.cond`` on ``any(here)``).  Exact
  because every global block lives on exactly one shard, so the per-hop
  partials partition each group's selected set.  Differentiated by plain
  autodiff under one outer ``jax.checkpoint`` — the backward replays the
  whole ring (rotations included) instead of saving per-hop gathered
  blocks.

Plus the host-side planner for segment-sharded packed-varlen batches:

* :func:`plan_segments` / :class:`SegmentPlan` — greedy LPT (longest
  processing time) partitioning of samples onto shards with cost ∝ nᵢ²
  (attention work is quadratic per sample), and :func:`axis_layout` /
  :func:`split_tokens` / :func:`merge_tokens` to re-lay the packed axis out
  as one contiguous padded slab per shard.  After the re-layout every BSA
  branch is segment-local (samples never attend each other), so the varlen
  ops run per shard with plain local offsets and ZERO collectives — the
  compression branch's ring degenerates to its hop-0 term because the
  pooled key axis is laid out with the same sample→shard assignment.
  Plans and layouts are LRU-cached on the concrete offsets.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.numerics import NEG_INF, mask_to_bias

__all__ = [
    "ring_perm",
    "ring_flash",
    "ring_selection",
    "SegmentPlan",
    "plan_segments",
    "lpt_partition",
    "round_robin_partition",
    "axis_layout",
    "split_tokens",
    "merge_tokens",
]

_TINY = 1e-20


def ring_perm(p: int) -> list[tuple[int, int]]:
    """The rotation permutation: shard j sends to (j+1) mod p, so after one
    ``ppermute`` shard i holds what its LEFT neighbour held — hop h leaves
    shard i holding the slab originated by shard (i − h) mod p."""
    return [(j, (j + 1) % p) for j in range(p)]


def _rotate(xs, axis, p):
    perm = ring_perm(p)
    return tuple(jax.lax.ppermute(x, axis, perm) for x in xs)


def _merge(m, l, acc, m_h, l_h, acc_h):
    """Online-softmax merge of two partial-attention statistics triples.

    m: running row max (…); l: running sum of exp (…); acc: running
    unnormalised output (…, D).  All-masked partials carry m = NEG_INF (or
    below) and l = 0, so they merge as exact no-ops."""
    m_new = jnp.maximum(m, m_h)
    a = jnp.exp(m - m_new)
    b = jnp.exp(m_h - m_new)
    return (m_new, a * l + b * l_h,
            a[..., None] * acc + b[..., None] * acc_h)


# ---------------------------------------------------------------------------
# ring flash — dense flash attention over rotating K/V slabs
# ---------------------------------------------------------------------------

def _flash_partial(qh, kh, vh, bias, rep):
    """One hop's partial stats.  qh (B,Hq,n,D) vs head-major slab kh/vh
    (B,Hkv,n,D); bias broadcastable to (B,1,n,n).  Returns fp32
    (m (B,Hq,n), l (B,Hq,n), acc (B,Hq,n,D))."""
    d = qh.shape[-1]
    if rep > 1:
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    logits = jnp.einsum("bhnd,bhld->bhnl", qh, kh,
                        preferred_element_type=jnp.float32) / (d ** 0.5)
    logits = logits + bias
    m = logits.max(-1)
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
    l = p.sum(-1)
    acc = jnp.einsum("bhnl,bhld->bhnd", p, vh.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return m, l, acc


def _hop_bias(kbias, i, src, n, causal):
    """(B, n) travelling key bias + the token-causal rule from GLOBAL
    positions (query shard i, key source shard src)."""
    bias = kbias[:, None, None, :]                          # (B,1,1,n)
    if causal:
        qpos = i * n + jnp.arange(n)
        kpos = src * n + jnp.arange(n)
        bias = bias + mask_to_bias(kpos[None, :] <= qpos[:, None])[None, None]
    return bias


@functools.lru_cache(maxsize=64)
def _ring_flash_fn(axis: str, p: int, causal: bool, live_key):
    """Build (and cache) the custom_vjp ring-flash core for one static
    configuration.  ``live_key``: hashable (p, p) hop-live table (rows =
    shard, cols = hop) or None = every hop computes."""
    live = None if live_key is None else np.asarray(live_key, bool)

    def _gated(pred, fn, carry):
        if pred is None:
            return fn(carry)
        return jax.lax.cond(pred, fn, lambda c: c, carry)

    def _hop_pred(i, h):
        if live is None:
            return None
        return jnp.asarray(live)[i, h]

    def _fwd_stats(q, k, v, kbias):
        B, n, Hq, D = q.shape
        rep = Hq // k.shape[2]
        i = jax.lax.axis_index(axis)
        qh = q.transpose(0, 2, 1, 3)
        kc = k.transpose(0, 2, 1, 3)
        vc = v.transpose(0, 2, 1, 3)
        bc = kbias
        m = jnp.full((B, Hq, n), NEG_INF, jnp.float32)
        l = jnp.zeros((B, Hq, n), jnp.float32)
        acc = jnp.zeros((B, Hq, n, D), jnp.float32)
        for h in range(p):
            src = (i - h) % p
            bias = _hop_bias(bc, i, src, n, causal)

            def hop(carry, kh=kc, vh=vc, bias=bias):
                mh, lh, ah = _flash_partial(qh, kh, vh, bias, rep)
                return _merge(*carry, mh, lh, ah)

            m, l, acc = _gated(_hop_pred(i, h), hop, (m, l, acc))
            if h < p - 1:
                kc, vc, bc = _rotate((kc, vc, bc), axis, p)
        out = acc / jnp.maximum(l, _TINY)[..., None]
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, _TINY)), NEG_INF)
        return out.transpose(0, 2, 1, 3), lse               # out core-layout

    @jax.custom_vjp
    def f(q, k, v, kbias):
        out, _ = _fwd_stats(q, k, v, kbias)
        return out.astype(v.dtype)

    def f_fwd(q, k, v, kbias):
        out, lse = _fwd_stats(q, k, v, kbias)
        return out.astype(v.dtype), (q, k, v, kbias, out, lse)

    def f_bwd(res, do):
        q, k, v, kbias, out, lse = res
        B, n, Hq, D = q.shape
        Hkv = k.shape[2]
        rep = Hq // Hkv
        i = jax.lax.axis_index(axis)
        qh = q.transpose(0, 2, 1, 3).astype(jnp.float32)
        doh = do.transpose(0, 2, 1, 3).astype(jnp.float32)
        delta = (doh * out.transpose(0, 2, 1, 3)).sum(-1)   # (B,Hq,n)
        scale = 1.0 / (D ** 0.5)
        kc = k.transpose(0, 2, 1, 3)
        vc = v.transpose(0, 2, 1, 3)
        bc = kbias
        dq = jnp.zeros((B, Hq, n, D), jnp.float32)
        dk = jnp.zeros((B, Hkv, n, D), jnp.float32)
        dv = jnp.zeros((B, Hkv, n, D), jnp.float32)
        for h in range(p):
            src = (i - h) % p
            bias = _hop_bias(bc, i, src, n, causal)

            def hop(carry, kh=kc, vh=vc, bias=bias):
                dq, dk, dv = carry
                khr = jnp.repeat(kh, rep, axis=1) if rep > 1 else kh
                vhr = jnp.repeat(vh, rep, axis=1) if rep > 1 else vh
                logits = jnp.einsum(
                    "bhnd,bhld->bhnl", qh, khr,
                    preferred_element_type=jnp.float32) * scale + bias
                ph = jnp.exp(logits - lse[..., None])
                ph = jnp.where(logits <= NEG_INF / 2, 0.0, ph)
                dp = jnp.einsum("bhnd,bhld->bhnl", doh,
                                vhr.astype(jnp.float32),
                                preferred_element_type=jnp.float32)
                dl = ph * (dp - delta[..., None])
                dq2 = dq + jnp.einsum("bhnl,bhld->bhnd", dl,
                                      khr.astype(jnp.float32)) * scale
                dkh = jnp.einsum("bhnl,bhnd->bhld", dl, qh) * scale
                dvh = jnp.einsum("bhnl,bhnd->bhld", ph, doh)
                if rep > 1:
                    dkh = dkh.reshape(B, Hkv, rep, n, D).sum(2)
                    dvh = dvh.reshape(B, Hkv, rep, n, D).sum(2)
                return dq2, dk + dkh, dv + dvh

            dq, dk, dv = _gated(_hop_pred(i, h), hop, (dq, dk, dv))
            # rotate EVERY iteration (p total): the slab — and the dK/dV it
            # accumulated while visiting — completes the revolution home
            kc, vc, bc, dk, dv = _rotate((kc, vc, bc, dk, dv), axis, p)
        return (dq.transpose(0, 2, 1, 3).astype(q.dtype),
                dk.transpose(0, 2, 1, 3).astype(k.dtype),
                dv.transpose(0, 2, 1, 3).astype(v.dtype),
                jnp.zeros_like(kbias))

    f.defvjp(f_fwd, f_bwd)
    return f


def ring_flash(q, k, v, kbias, *, axis: str, p: int, causal: bool = False,
               live=None):
    """Sequence-sharded flash attention (call INSIDE shard_map).

    q (B, n, Hq, D), k/v (B, n, Hkv, D), kbias (B, n) fp32 additive key bias
    (0 = attend, NEG_INF = masked) — all LOCAL slabs of a length-p·n global
    sequence sharded along mesh axis ``axis``.  ``causal`` applies the
    token-causal rule on GLOBAL positions; ``live`` is an optional (p, p)
    hop-live table (see ``occupancy.ring_hop_live``) gating per-hop compute.
    Returns the local (B, n, Hq, D) output slab.  Differentiable in q/k/v
    (kbias gets zero cotangent) with O(n) backward memory via per-hop
    recompute from the saved logsumexp."""
    live_key = None
    if live is not None:
        live_key = tuple(tuple(bool(x) for x in row)
                         for row in np.asarray(live))
    return _ring_flash_fn(axis, p, bool(causal), live_key)(q, k, v, kbias)


# ---------------------------------------------------------------------------
# ring selection — rotating K/V for the top-k gathered-block branch
# ---------------------------------------------------------------------------

def _selection_partial(qh, kc, vc, mc, loc, here, ell, scale_dim):
    """Partial stats of one selection hop.

    qh (B,Hkv,G,rep,g,D) head-major grouped queries; kc/vc (B,n,Hkv,D) the
    RESIDENT slab; mc (B,n) int32 token validity of the slab; loc
    (B,G,Hkv,k*) slab-local block indices with ``here`` marking selections
    resident on this slab.  Mirrors ``branches.gather_attend_blocks`` but
    returns unnormalised (m, l, acc) for the online merge."""
    B, n, Hkv, D = kc.shape
    nb = n // ell
    k_star = loc.shape[-1]
    G = loc.shape[1]
    L = k_star * ell
    safe = jnp.where(here, loc, 0)
    ig = safe.transpose(0, 2, 1, 3).reshape(B, Hkv, G * k_star)
    kb = kc.reshape(B, nb, ell, Hkv, D).transpose(0, 3, 1, 2, 4)
    vb = vc.reshape(B, nb, ell, Hkv, D).transpose(0, 3, 1, 2, 4)
    kg = jnp.take_along_axis(kb.reshape(B, Hkv, nb, ell * D),
                             ig[..., None], axis=2).reshape(B, Hkv, G, L, D)
    vg = jnp.take_along_axis(vb.reshape(B, Hkv, nb, ell * D),
                             ig[..., None], axis=2).reshape(B, Hkv, G, L, D)
    valid = jnp.broadcast_to(
        here.transpose(0, 2, 1, 3)[..., None], (B, Hkv, G, k_star, ell))
    tv = jnp.take_along_axis(mc.reshape(B, 1, nb, ell), ig[..., None],
                             axis=2) > 0
    valid = valid & tv.reshape(B, Hkv, G, k_star, ell)
    bias = mask_to_bias(valid.reshape(B, Hkv, G, 1, 1, L))
    logits = jnp.einsum("bhgrmd,bhgld->bhgrml", qh, kg,
                        preferred_element_type=jnp.float32) / (scale_dim ** 0.5)
    logits = logits + bias
    m = logits.max(-1)
    ph = jnp.exp(logits - m[..., None])
    ph = jnp.where(logits <= NEG_INF / 2, 0.0, ph)
    l = ph.sum(-1)
    acc = jnp.einsum("bhgrml,bhgld->bhgrmd", ph, vg.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return m, l, acc


def ring_selection(q, k, v, top_idx, sel_valid, key_valid, q_valid, *,
                   axis: str, p: int, block_size: int, group_size: int):
    """Sequence-sharded selection attention (call INSIDE shard_map).

    q (B, n, Hq, D) local queries; k/v (B, n, Hkv, D) the LOCAL K/V slab;
    top_idx/sel_valid (B, G_loc, Hkv, k*) this shard's groups with GLOBAL
    block indices; key_valid/q_valid (B, n) bool local validity.  Each hop
    re-bases the indices to the resident slab's coordinates and attends only
    the selections that live there; hops holding none are skipped at
    runtime.  Exact vs the replicated oracle because every global block is
    resident on exactly one shard (the hop partials partition each group's
    selected set).  Plain autodiff under an outer ``jax.checkpoint``: the
    backward replays the ring instead of saving per-hop gathers, so grads
    cost one extra revolution and O(n) memory."""
    from repro.kernels.occupancy import invalidate_dead_groups

    ell = block_size
    B, n, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    nb = n // ell
    G = top_idx.shape[1]
    g = group_size
    sel_valid = invalidate_dead_groups(sel_valid, q_valid, n)
    mc0 = (jnp.ones((B, n), jnp.int32) if key_valid is None
           else key_valid.astype(jnp.int32))

    def core(q, k, v, top_idx, sel_valid, mc):
        i = jax.lax.axis_index(axis)
        qh = q.reshape(B, G, g, Hkv, rep, D).transpose(0, 3, 1, 4, 2, 5)
        m = jnp.full((B, Hkv, G, rep, g), NEG_INF, jnp.float32)
        l = jnp.zeros((B, Hkv, G, rep, g), jnp.float32)
        acc = jnp.zeros((B, Hkv, G, rep, g, D), jnp.float32)
        kc, vc = k, v
        for h in range(p):
            src = (i - h) % p
            loc = top_idx - src * nb
            here = sel_valid & (loc >= 0) & (loc < nb)

            def hop(carry, kc=kc, vc=vc, mc=mc, loc=loc, here=here):
                mh, lh, ah = _selection_partial(qh, kc, vc, mc, loc, here,
                                                ell, D)
                return _merge(*carry, mh, lh, ah)

            # runtime dead-hop skip: the rotation below still runs on every
            # shard (collective), only the gather+matmuls are elided
            m, l, acc = jax.lax.cond(jnp.any(here), hop, lambda c: c,
                                     (m, l, acc))
            if h < p - 1:
                kc, vc, mc = _rotate((kc, vc, mc), axis, p)
        # a group with no valid selection has l = acc = 0 and returns 0;
        # dividing by 1 there keeps the backward finite (acc/max(l, tiny)
        # differentiates through tiny**-2, which overflows to inf)
        out = acc / jnp.where(l > 0, l, 1.0)[..., None]
        out = out.transpose(0, 2, 4, 1, 3, 5).reshape(B, n, Hq, D)
        return out.astype(v.dtype)

    return jax.checkpoint(core)(q, k, v, top_idx, sel_valid, mc0)


# ---------------------------------------------------------------------------
# segment-sharded packed-varlen: LPT planner + axis re-layout
# ---------------------------------------------------------------------------

def lpt_partition(sizes, p: int) -> tuple:
    """Greedy LPT: samples in decreasing cost order (cost ∝ nᵢ², attention
    work is quadratic per sample) each go to the least-loaded shard.
    Returns the shard id per sample.  Classic 4/3-approximation of the
    optimal makespan — the skew test shows it beating round-robin by >1.5×
    on adversarial mixes."""
    sizes = np.asarray(sizes, np.int64)
    order = np.argsort(-(sizes.astype(np.float64) ** 2), kind="stable")
    loads = np.zeros(p, np.float64)
    assign = np.zeros(len(sizes), np.int64)
    for s in order:
        j = int(np.argmin(loads))
        assign[s] = j
        loads[j] += float(sizes[s]) ** 2
    return tuple(int(a) for a in assign)


def round_robin_partition(sizes, p: int) -> tuple:
    """Naive baseline: sample i → shard i mod p (what the skew test beats)."""
    return tuple(i % p for i in range(len(sizes)))


@dataclasses.dataclass(frozen=True)
class SegmentPlan:
    """A sample→shard assignment for one packed-varlen batch.

    All fields are plain hashable tuples/ints so the plan itself keys the
    layout LRU.  ``cost_balance`` = max/mean per-shard Σnᵢ² (1.0 = perfect)."""

    p: int
    sizes: tuple            # (S,) token count per sample (trailing 0s = empty)
    assign: tuple           # (S,) shard id per sample

    @property
    def loads(self) -> tuple:
        out = [0] * self.p
        for sz, a in zip(self.sizes, self.assign):
            out[a] += sz
        return tuple(out)

    @property
    def cost_balance(self) -> float:
        cost = [0.0] * self.p
        for sz, a in zip(self.sizes, self.assign):
            cost[a] += float(sz) ** 2
        mean = sum(cost) / self.p
        return max(cost) / mean if mean else 1.0


@functools.lru_cache(maxsize=128)
def plan_segments(offsets: tuple, p: int,
                  partition=lpt_partition) -> SegmentPlan:
    """LPT-assign the samples of a CONCRETE offsets tuple to ``p`` shards."""
    sizes = tuple(int(b - a) for a, b in zip(offsets[:-1], offsets[1:]))
    return SegmentPlan(p=p, sizes=sizes, assign=partition(sizes, p))


@functools.lru_cache(maxsize=256)
def axis_layout(plan: SegmentPlan, offsets: tuple, total: int,
                pad_to: int = 1):
    """Per-shard contiguous re-layout of one packed axis.

    ``offsets`` are THIS axis's sample boundaries (the selection/ball token
    axis, or the compression branch's pooled block axis — any axis whose
    samples follow ``plan.assign``); ``total`` its global capacity.  Returns
    ``(idx, local_offsets, capacity, shift)``:

    * idx (p·capacity,) int32 — global position of each local slot, with the
      one-past-end index ``total`` marking padding slots (gathers pull a
      zero row, scatters land on a sliced-off row);
    * local_offsets (p, S+1) int32 — per-shard varlen offsets, trailing
      repeats for the samples a shard does not own (empty segments per the
      packed-varlen contract);
    * capacity int — per-shard padded length (max load rounded up to
      ``pad_to``, at least ``pad_to``);
    * shift (S,) int32 — local_start − global_start per sample (index
      re-basing for selection's global block coordinates).
    """
    starts = np.asarray(offsets[:-1], np.int64)
    ends = np.asarray(offsets[1:], np.int64)
    sizes = ends - starts
    loads = np.zeros(plan.p, np.int64)
    local_start = np.zeros(len(sizes), np.int64)
    for s, a in enumerate(plan.assign):
        local_start[s] = loads[a]
        loads[a] += sizes[s]
    capacity = max(int(loads.max()), 1)
    capacity = -(-capacity // pad_to) * pad_to
    idx = np.full((plan.p, capacity), total, np.int32)
    local_offsets = np.zeros((plan.p, len(offsets)), np.int32)
    for s, a in enumerate(plan.assign):
        idx[a, local_start[s]:local_start[s] + sizes[s]] = np.arange(
            starts[s], ends[s], dtype=np.int32)
        local_offsets[:, s + 1] = local_offsets[:, s]
        local_offsets[a, s + 1] = local_start[s] + sizes[s]
    shift = (local_start - starts).astype(np.int32)
    return idx.reshape(-1), local_offsets, capacity, shift


def split_tokens(idx, arr, p: int):
    """(T, …) global packed array → (p, capacity, …) per-shard slabs via a
    layout's gather index (padding slots read a zero row)."""
    pad = jnp.zeros((1,) + arr.shape[1:], arr.dtype)
    return jnp.concatenate([arr, pad], 0)[jnp.asarray(idx)].reshape(
        (p, -1) + arr.shape[1:])


def merge_tokens(idx, parts, total: int):
    """(p, capacity, …) per-shard outputs → (T, …) global packed array.
    Padding slots scatter onto the sliced-off row ``total``; global rows no
    sample owns (the capacity tail) come back exactly zero."""
    flat = parts.reshape((-1,) + parts.shape[2:])
    out = jnp.zeros((total + 1,) + flat.shape[1:], flat.dtype)
    return out.at[jnp.asarray(idx)].set(flat)[:total]


def lcm(a: int, b: int) -> int:
    return abs(a * b) // math.gcd(a, b) if a and b else max(a, b)
