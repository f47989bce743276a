"""Multi-device attention backend: ``shard_map``ped BSA over a device mesh.

The ``"sharded"`` backend wraps any inner single-device backend
(``"jnp"`` / ``"pallas"`` / ``"interpret"``) and runs the four GQA-native
ops of the backend protocol under :func:`jax.shard_map` on the
mesh activated by :func:`mesh_context` — the distributed analogue of
``use_backend()``:

    with mesh_context(make_local_mesh()), use_backend("sharded"):
        out = bsa_attention(params, q, k, v, cfg=cfg)   # no call-site change

Per-branch sharding strategy (see docs/distributed.md for the full table):

* ``ball`` — ball-axis DATA parallelism.  Balls are independent attention
  units, so the sequence dim is sharded in ball-multiple chunks and the
  inner backend runs unmodified per shard.  **No collectives.**
* ``local_window`` — sequence sharded in window-multiple chunks plus a
  one-block **halo exchange** (``lax.ppermute``): each shard receives its
  left neighbour's last block of K/V so block 0 of the shard can attend its
  previous block.  Shard 0's halo arrives zero-filled with an all-False
  mask, which reproduces the reference's first-block rule exactly.
* ``flash`` — CONTEXT parallelism.  Non-causal / block-causal: queries
  sharded, the T/ℓ-small compressed K/V replicated (the implicit all-gather
  is cheap by construction).  TOKEN-CAUSAL flash runs the
  :func:`repro.distributed.ring.ring_flash` primitive instead: q, K and V
  all sequence-sharded, K/V slabs rotating via ``lax.ppermute`` with
  online-softmax merging, and the static hop-live table
  (``occupancy.ring_hop_live``) skipping the ~half of the hops the causal
  mask kills.  Per-shard K/V memory O(L/p), p−1 hops of (B·L/p·Hkv·D)
  bytes each.
* ``selection`` — queries, selected indices and validity sharded along the
  group axis AND K/V + key mask sequence-sharded:
  :func:`repro.distributed.ring.ring_selection` rotates the K/V slabs,
  re-bases the global top-k block indices to each resident slab's
  coordinates, attends only the selections that live there, and skips hops
  that hold none at runtime.  Nothing is replicated any more.
* packed-varlen (``*_varlen``) — SEGMENT sharding.  A greedy LPT partition
  (cost ∝ nᵢ², :func:`repro.distributed.ring.plan_segments`) assigns
  samples to shards, the packed axis is re-laid out as one contiguous
  padded slab per shard, and the inner backend's varlen ops run per shard
  on plain LOCAL offsets — samples never attend each other, so ball, local,
  selection (indices re-based by the per-sample shift) and the compression
  flash (its pooled block axis laid out with the SAME assignment, i.e. the
  ring's hop-0 term) all run with ZERO collectives.  Needs CONCRETE
  offsets: traced offsets (jit without static boundaries) fall back with a
  warning.

Gradients: ``shard_map``'s transpose rule psums cotangents of replicated
inputs and transposes ``ppermute`` to the reverse rotation, so all paths —
including the hand-written ring-flash ``custom_vjp`` and the re-layout
gathers — stay shard-correct with no extra code.

Whenever an op cannot shard (indivisible sizes, traced offsets, 1-device
mesh) it falls back to the inner backend unsharded and warns ONCE per
(op, cause) — numerics never change, only the partitioning.

The module also provides :func:`sharded_paged_decode`: the paged NSA decode
step with the KV pools row-partitioned across the mesh axis
(``core.nsa_causal`` dispatches here when the resolved backend is sharded).
Its compression branch reuses the ring's statistics merge: each shard
attends its OWN compressed rows and only the (m, l, acc) triples are
psum-merged — an O(B·Hq·D) collective instead of all-gathering the
O(B·NB·Hkv·D) compressed K/V (set ``REPRO_SHARDED_RING_DECODE=0`` to
restore the gather+psum path).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import warnings

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.backend import (
    get_backend,
    get_varlen,
    list_backends,
    register_backend,
)
from repro.distributed import ring
from repro.distributed.sharding import axis_rules, logical_to_spec

__all__ = [
    "ShardedBackend",
    "mesh_context",
    "current_mesh_axis",
    "sharded_paged_decode",
    "reset_warnings",
]


# ---------------------------------------------------------------------------
# mesh_context — the distributed analogue of use_backend()
# ---------------------------------------------------------------------------

_TLS = threading.local()
_WARNED: set = set()


def _warn_once(op: str, code: str, detail: str) -> None:
    """Warn once per (op, cause).  ``code`` is a STABLE cause identifier —
    ``detail`` may embed dynamic shapes, so keying on it (or on the op
    alone) would either re-warn per shape or let one cause suppress a
    different one for the same op."""
    key = (op, code)
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(f"sharded backend: {op} falls back to the inner "
                      f"backend unsharded [{code}] — {detail}",
                      RuntimeWarning, stacklevel=3)


def reset_warnings() -> None:
    """Clear the warn-once registry (test isolation)."""
    _WARNED.clear()


@contextlib.contextmanager
def mesh_context(mesh, *, axis: str = "data", rules: dict | None = None):
    """Activate ``mesh`` for the ``"sharded"`` backend (trace-time scoped).

    ``axis`` names the mesh axis the sequence/ball dim is sharded over.
    Also enters :func:`repro.distributed.sharding.axis_rules` so ``constrain``
    annotations in ``core`` resolve against the same mesh: the merged rules
    point ``seq_sp`` at ``axis`` and stop ``batch`` from grabbing it first
    (override via ``rules`` for batch-parallel setups).
    """
    if axis not in mesh.shape:
        raise ValueError(f"mesh_context: axis {axis!r} not in mesh axes "
                         f"{tuple(mesh.shape)}")
    merged = {"batch": None, "seq_sp": (axis,)}
    if rules:
        merged.update(rules)
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append((mesh, axis))
    try:
        with axis_rules(mesh, merged):
            yield mesh
    finally:
        stack.pop()


def current_mesh_axis():
    """(mesh, axis) of the innermost active :func:`mesh_context`, or None."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


# ---------------------------------------------------------------------------
# shard_map plumbing helpers
# ---------------------------------------------------------------------------

def _shard_call(mesh, body, arg_specs, out_specs):
    """shard_map with None-arg skipping.

    ``arg_specs``: list of (array-or-None, PartitionSpec).  None entries are
    closed over (shard_map cannot spec them) and re-inserted so ``body``
    always receives the full positional list.
    """
    args = [a for a, _ in arg_specs if a is not None]
    specs = tuple(s for a, s in arg_specs if a is not None)
    present = [a is not None for a, _ in arg_specs]

    def wrapper(*xs):
        it = iter(xs)
        return body(*[next(it) if pr else None for pr in present])

    return shard_map(wrapper, mesh=mesh, in_specs=specs,
                     out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# The backend
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedBackend:
    """shard_map wrapper around an inner backend (see module docstring).

    ``inner`` pins the wrapped backend by name; None defers to the
    ``REPRO_SHARDED_INNER`` env var, then ``"auto"`` (pallas on TPU, jnp
    elsewhere).  The mesh is NOT stored here — it is resolved at trace time
    from the ambient :func:`mesh_context`, exactly like ``use_backend``
    resolves the backend name.
    """

    name: str = "sharded"
    inner: str | None = None
    requires_mesh = True         # engines fail fast without a mesh_context
    is_sharded_backend = True    # decode dispatch marker (core.nsa_causal)

    # -- resolution ---------------------------------------------------------

    def _resolve_inner(self):
        name = os.environ.get("REPRO_SHARDED_INNER") or self.inner or "auto"
        if name == "sharded":
            raise ValueError("the sharded backend cannot wrap itself "
                             "(REPRO_SHARDED_INNER/inner must name a "
                             "single-device backend)")
        return get_backend(name)

    def _require_mesh(self, op: str):
        ctx = current_mesh_axis()
        if ctx is None:
            raise RuntimeError(
                f"the 'sharded' backend needs an active mesh to run {op!r}; "
                "wrap the call (or trace) in\n"
                "    from repro.distributed import mesh_context\n"
                "    from repro.launch.mesh import make_local_mesh\n"
                "    with mesh_context(make_local_mesh()):\n"
                "        ...\n"
                "(on CPU, XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                "fakes a mesh for smoke runs)")
        return ctx

    def _plan(self, op: str, mesh, axis, n: int, granule: int = 1):
        """Shard count p along ``axis`` if dim ``n`` can shard, else None.

        Routes through ``logical_to_spec`` so divisibility failures surface
        through its one-shot warning, then applies the op's granule rule
        (per-shard length must stay a multiple of the ball/window size).
        """
        p = mesh.shape[axis]
        if p == 1:
            return None
        spec = logical_to_spec(("seq_shard",), (n,), mesh,
                               {"seq_shard": (axis,)})
        if spec[0] is None:
            _warn_once(op, "indivisible-dim",
                       f"dim {n} not divisible by mesh axis {axis!r}={p}")
            return None
        if (n // p) % granule:
            _warn_once(op, "granule",
                       f"per-shard length {n // p} not a multiple of "
                       f"granule {granule} (dim {n}, {axis!r}={p})")
            return None
        return p

    def _segment_plan(self, op: str, mesh, axis, offsets, granules=()):
        """LPT sample→shard plan for a packed-varlen op, or None → fallback.

        Needs CONCRETE offsets (the partition is a host-side decision) and
        every sample size divisible by each granule (so the re-laid-out
        local starts keep block/group boundaries aligned)."""
        from repro.kernels.occupancy import offsets_digest
        p = mesh.shape[axis]
        if p == 1:
            return None
        dig = offsets_digest(offsets)
        if dig is None:
            _warn_once(op, "traced-offsets",
                       "offsets are traced (jitted without concrete "
                       "boundaries); the LPT segment partition is a "
                       "host-side decision")
            return None
        sizes = [b - a for a, b in zip(dig[:-1], dig[1:])]
        for gr in granules:
            if gr > 1 and any(sz % gr for sz in sizes):
                _warn_once(op, "granule",
                           f"sample sizes not all multiples of granule {gr}")
                return None
        return p, ring.plan_segments(dig, p), dig

    # -- dense ops ----------------------------------------------------------

    def ball(self, q, k, v, mask, *, ball_size, chunk_tokens=0):
        mesh, axis = self._require_mesh("ball")
        inner = self._resolve_inner()
        if self._plan("ball", mesh, axis, q.shape[1], ball_size) is None:
            return inner.ball(q, k, v, mask, ball_size=ball_size,
                              chunk_tokens=chunk_tokens)
        seq = P(None, axis)

        def body(q, k, v, m):
            return inner.ball(q, k, v, m, ball_size=ball_size,
                              chunk_tokens=chunk_tokens)

        return _shard_call(mesh, body,
                           [(q, seq), (k, seq), (v, seq), (mask, seq)], seq)

    def local_window(self, q, k, v, *, window, mask=None, chunk_tokens=0):
        mesh, axis = self._require_mesh("local_window")
        inner = self._resolve_inner()
        p = self._plan("local_window", mesh, axis, q.shape[1], window)
        if p is None:
            return inner.local_window(q, k, v, window=window, mask=mask,
                                      chunk_tokens=chunk_tokens)
        if mask is None:
            mask = jnp.ones(q.shape[:2], bool)   # ones ≡ None numerically
        seq = P(None, axis)
        w = window
        perm = [(i, i + 1) for i in range(p - 1)]   # shard 0 gets zero-fill

        def body(q, k, v, m):
            # halo exchange: left neighbour's last block, so this shard's
            # block 0 can attend its previous block; the zero query block
            # keeps the inner's blocked layout aligned and is sliced off
            hk = jax.lax.ppermute(k[:, -w:], axis, perm)
            hv = jax.lax.ppermute(v[:, -w:], axis, perm)
            hm = jax.lax.ppermute(m[:, -w:].astype(jnp.int32), axis, perm) > 0
            out = inner.local_window(
                jnp.concatenate([jnp.zeros_like(q[:, :w]), q], axis=1),
                jnp.concatenate([hk, k], axis=1),
                jnp.concatenate([hv, v], axis=1),
                window=w,
                mask=jnp.concatenate([hm, m], axis=1),
                chunk_tokens=chunk_tokens)
            return out[:, w:]

        return _shard_call(mesh, body,
                           [(q, seq), (k, seq), (v, seq), (mask, seq)], seq)

    def flash(self, q, k, v, *, key_valid=None, causal=False,
              block_causal=False, ell=1, chunk_tokens=0, q_valid=None):
        from repro.core.backend import accepts_kwarg

        mesh, axis = self._require_mesh("flash")
        inner = self._resolve_inner()
        inner_kw = {}
        if q_valid is not None and accepts_kwarg(inner.flash, "q_valid"):
            inner_kw["q_valid"] = q_valid
        N, L = q.shape[1], k.shape[1]
        if causal:
            # ring flash: q, K and V all sequence-sharded, K/V rotating —
            # the token-causal rule needs aligned q/k axes to place global
            # positions, which holds whenever N == L (the dense-baseline
            # layout; decode's right-aligned N < L stays unsharded)
            p = self._plan("flash", mesh, axis, N) if N == L else None
            if N != L:
                _warn_once("flash", "causal-qk-mismatch",
                           f"token-causal q len {N} != k len {L} "
                           "(right-aligned decode layout) cannot ring-shard")
            if p is None:
                return inner.flash(q, k, v, key_valid=key_valid, causal=True,
                                   block_causal=block_causal, ell=ell,
                                   chunk_tokens=chunk_tokens, **inner_kw)
            from repro.kernels import occupancy
            from repro.numerics import key_padding_bias

            live = occupancy.ring_hop_live(p, N // p, causal=True)
            occupancy.record("ring_flash", live)
            kb = key_padding_bias(key_valid, q.shape[0], L)
            seq = P(None, axis)

            def body(q, k, v, kb):
                return ring.ring_flash(q, k, v, kb, axis=axis, p=p,
                                       causal=True, live=live)

            return _shard_call(mesh, body,
                               [(q, seq), (k, seq), (v, seq), (kb, seq)],
                               seq)
        p = self._plan("flash", mesh, axis, N)
        if p is None:
            return inner.flash(q, k, v, key_valid=key_valid,
                               block_causal=block_causal, ell=ell,
                               chunk_tokens=chunk_tokens, **inner_kw)
        seq = P(None, axis)
        n_loc = N // p

        if block_causal:
            # the block-causal rule depends on GLOBAL query position; the
            # shard offset is traced (axis_index), which a kernel grid
            # cannot take — so the sharded path computes the branch with
            # the reference math + pos0 (exact parity with inner="jnp")
            from repro.core.branches import chunked_q_attention, repeat_kv

            def body(q, k, v, kv):
                pos0 = jax.lax.axis_index(axis) * n_loc
                rep = q.shape[2] // k.shape[2]
                return chunked_q_attention(
                    q, repeat_kv(k, rep), repeat_kv(v, rep), key_valid=kv,
                    block_causal_ell=ell, chunk=chunk_tokens, pos0=pos0)
        else:
            def body(q, k, v, kv):
                kw = dict(inner_kw)
                if "q_valid" in kw:
                    kw["q_valid"] = None   # global hint, wrong per shard
                return inner.flash(q, k, v, key_valid=kv, ell=ell,
                                   chunk_tokens=chunk_tokens, **kw)

        # non-causal flash is the compression branch: K/V are the T/ℓ-small
        # pooled blocks, so replicating them is cheap by construction
        return _shard_call(mesh, body,
                           [(q, seq), (k, P()), (v, P()),
                            (key_valid, P())], seq)

    def selection(self, q, k, v, top_idx, sel_valid, mask, *, block_size,
                  group_size, chunk_tokens=0, q_valid=None):
        mesh, axis = self._require_mesh("selection")
        inner = self._resolve_inner()
        N, L, G = q.shape[1], k.shape[1], top_idx.shape[1]
        # ring selection shards K/V too, so the sequence must split in
        # block-size granules and the group axis in equal per-shard counts
        p = self._plan("selection", mesh, axis, N,
                       ring.lcm(block_size, N // G)) if N == L else None
        if N != L:
            _warn_once("selection", "qk-mismatch",
                       f"q len {N} != k len {L}; ring rotation needs "
                       "aligned sequence slabs")
        if p is not None and G % p:
            _warn_once("selection", "groups-indivisible",
                       f"G={G} not divisible by {axis!r}={p}")
            p = None
        if p is None:
            return inner.selection(q, k, v, top_idx, sel_valid, mask,
                                   block_size=block_size,
                                   group_size=group_size,
                                   chunk_tokens=chunk_tokens)
        if mask is None:
            mask = jnp.ones(q.shape[:2], bool)
        qv = mask if q_valid is None else q_valid
        seq = P(None, axis)
        g = N // G

        def body(q, ti, sv, k, v, m, qv):
            return ring.ring_selection(q, k, v, ti, sv, m, qv, axis=axis,
                                       p=p, block_size=block_size,
                                       group_size=g)

        return _shard_call(
            mesh, body,
            [(q, seq), (top_idx, seq), (sel_valid, seq),
             (k, seq), (v, seq),       # K/V stay sharded and rotate
             (mask, seq),              # key-token validity: local slab
             (qv, seq)],               # query validity: this shard's slice
            seq)

    # -- packed-varlen ops: LPT segment sharding ----------------------------

    def _varlen_layouts(self, plan, dig, total, pad_to):
        idx, loff, _, shift = ring.axis_layout(plan, dig, total, pad_to)
        return idx, jnp.asarray(loff), shift

    def ball_varlen(self, q, k, v, offsets, mask, *, ball_size,
                    chunk_tokens=0):
        mesh, axis = self._require_mesh("ball_varlen")
        inner = self._resolve_inner()
        planned = self._segment_plan("ball_varlen", mesh, axis, offsets,
                                     granules=(ball_size,))
        op = get_varlen(inner, "ball")
        if planned is None:
            return op(q, k, v, offsets, mask, ball_size=ball_size,
                      chunk_tokens=chunk_tokens)
        p, plan, dig = planned
        T = q.shape[0]
        idx, loff, _ = self._varlen_layouts(plan, dig, T, ball_size)
        qs, ks, vs = (ring.split_tokens(idx, a, p) for a in (q, k, v))
        ms = None if mask is None else ring.split_tokens(idx, mask, p)
        sp = P(axis)

        def body(q, k, v, m, lo):
            out = op(q[0], k[0], v[0], lo[0],
                     None if m is None else m[0],
                     ball_size=ball_size, chunk_tokens=chunk_tokens)
            return out[None]

        parts = _shard_call(mesh, body,
                            [(qs, sp), (ks, sp), (vs, sp), (ms, sp),
                             (loff, sp)], sp)
        return ring.merge_tokens(idx, parts, T)

    def flash_varlen(self, q, k, v, q_offsets, k_offsets, *, key_valid=None,
                     chunk_tokens=0):
        """Compression-branch varlen flash, segment-sharded on BOTH axes.

        The pooled key axis is laid out with the SAME sample→shard
        assignment as the query axis, so every query's keys are resident —
        this is the ring schedule with only hop 0 live, i.e. zero
        collectives."""
        from repro.kernels.occupancy import offsets_digest

        mesh, axis = self._require_mesh("flash_varlen")
        inner = self._resolve_inner()
        op = get_varlen(inner, "flash")
        p = mesh.shape[axis]
        qd, kd = offsets_digest(q_offsets), offsets_digest(k_offsets)
        if p == 1 or qd is None or kd is None:
            if p > 1:
                _warn_once("flash_varlen", "traced-offsets",
                           "offsets are traced (jitted without concrete "
                           "boundaries); the LPT segment partition is a "
                           "host-side decision")
            return op(q, k, v, q_offsets, k_offsets, key_valid=key_valid,
                      chunk_tokens=chunk_tokens)
        plan = ring.plan_segments(qd, p)
        Tq, Lk = q.shape[0], k.shape[0]
        qidx, qloff, _ = self._varlen_layouts(plan, qd, Tq, 1)
        kidx, kloff, _ = self._varlen_layouts(plan, kd, Lk, 1)
        qs = ring.split_tokens(qidx, q, p)
        ks, vs = (ring.split_tokens(kidx, a, p) for a in (k, v))
        kvs = (None if key_valid is None
               else ring.split_tokens(kidx, key_valid, p))
        sp = P(axis)

        def body(q, k, v, kv, qlo, klo):
            out = op(q[0], k[0], v[0], qlo[0], klo[0],
                     key_valid=None if kv is None else kv[0],
                     chunk_tokens=chunk_tokens)
            return out[None]

        parts = _shard_call(mesh, body,
                            [(qs, sp), (ks, sp), (vs, sp), (kvs, sp),
                             (qloff, sp), (kloff, sp)], sp)
        return ring.merge_tokens(qidx, parts, Tq)

    def local_window_varlen(self, q, k, v, offsets, *, window, mask=None,
                            chunk_tokens=0):
        mesh, axis = self._require_mesh("local_window_varlen")
        inner = self._resolve_inner()
        planned = self._segment_plan("local_window_varlen", mesh, axis,
                                     offsets, granules=(window,))
        op = get_varlen(inner, "local_window")
        if planned is None:
            return op(q, k, v, offsets, window=window, mask=mask,
                      chunk_tokens=chunk_tokens)
        p, plan, dig = planned
        T = q.shape[0]
        idx, loff, _ = self._varlen_layouts(plan, dig, T, window)
        qs, ks, vs = (ring.split_tokens(idx, a, p) for a in (q, k, v))
        ms = None if mask is None else ring.split_tokens(idx, mask, p)
        sp = P(axis)

        def body(q, k, v, m, lo):
            out = op(q[0], k[0], v[0], lo[0], window=window,
                     mask=None if m is None else m[0],
                     chunk_tokens=chunk_tokens)
            return out[None]

        parts = _shard_call(mesh, body,
                            [(qs, sp), (ks, sp), (vs, sp), (ms, sp),
                             (loff, sp)], sp)
        return ring.merge_tokens(idx, parts, T)

    def selection_varlen(self, q, k, v, top_idx, sel_valid, offsets, mask, *,
                         block_size, group_size, chunk_tokens=0):
        """Segment-sharded varlen selection.

        Selection never crosses samples (the scores mask enforces it), so
        after the LPT re-layout every group's selected blocks are resident
        on its own shard — the global block indices just need re-basing by
        the per-sample shift.  Needs sample sizes divisible by
        lcm(block, group) so block and group boundaries survive the move."""
        import numpy as np

        mesh, axis = self._require_mesh("selection_varlen")
        inner = self._resolve_inner()
        gran = ring.lcm(block_size, group_size)
        planned = self._segment_plan("selection_varlen", mesh, axis, offsets,
                                     granules=(gran,))
        op = get_varlen(inner, "selection")
        if planned is None:
            return op(q, k, v, top_idx, sel_valid, offsets, mask,
                      block_size=block_size, group_size=group_size,
                      chunk_tokens=chunk_tokens)
        p, plan, dig = planned
        T, G = q.shape[0], top_idx.shape[0]
        idx, loff, shift = self._varlen_layouts(plan, dig, T, gran)
        gdig = tuple(o // group_size for o in dig)
        gidx, _, _ = self._varlen_layouts(plan, gdig, G, gran // group_size)
        # per-group block-index shift: groups [off[s]/g, off[s+1]/g) belong
        # to sample s, whose blocks moved by shift[s]/ℓ
        gshift = np.zeros(G, np.int32)
        for s in range(len(dig) - 1):
            gshift[gdig[s]:gdig[s + 1]] = shift[s] // block_size
        ti = top_idx + jnp.asarray(gshift)[:, None, None]
        tis = ring.split_tokens(gidx, ti, p)
        svs = ring.split_tokens(gidx, sel_valid, p)
        qs, ks, vs = (ring.split_tokens(idx, a, p) for a in (q, k, v))
        ms = None if mask is None else ring.split_tokens(idx, mask, p)
        sp = P(axis)

        def body(q, k, v, ti, sv, m, lo):
            out = op(q[0], k[0], v[0], ti[0], sv[0], lo[0],
                     None if m is None else m[0],
                     block_size=block_size, group_size=group_size,
                     chunk_tokens=chunk_tokens)
            return out[None]

        parts = _shard_call(mesh, body,
                            [(qs, sp), (ks, sp), (vs, sp), (tis, sp),
                             (svs, sp), (ms, sp), (loff, sp)], sp)
        return ring.merge_tokens(idx, parts, T)


# ---------------------------------------------------------------------------
# Sequence-sharded paged decode (ServingEngine integration)
# ---------------------------------------------------------------------------

class _ShardedPoolOps:
    """Row-partitioned pool access for the paged decode.

    Pools are split along dim 0 into contiguous row blocks, one per shard.
    Gathers read OOB-safe locally (``mode="fill"`` zeros for rows another
    shard owns) and psum — exact, since every row has one nonzero
    contributor.  Scatters drop non-owned rows (``mode="drop"``), so each
    row is written only by its owner and no collective is needed.
    ``cmp_attend`` merges per-shard softmax statistics instead of gathering
    the compressed rows (the ring merge at hop count 1)."""

    def __init__(self, axis: str):
        self.axis = axis

    def _local(self, pool, rows):
        # rows this shard does not own map to r_loc — PAST the local end, so
        # fill/drop modes treat them as OOB.  (A bare negative index would
        # WRAP per Python indexing semantics before the OOB check.)
        r_loc = pool.shape[0]
        li = rows - jax.lax.axis_index(self.axis) * r_loc
        return jnp.where((li >= 0) & (li < r_loc), li, r_loc)

    def gather(self, pool, rows):
        g = pool.at[self._local(pool, rows)].get(mode="fill", fill_value=0)
        return jax.lax.psum(g, self.axis)

    def gather_head(self, pool, rows, head_idx):
        hb = jnp.broadcast_to(head_idx, rows.shape)
        g = pool.at[self._local(pool, rows), hb].get(mode="fill",
                                                     fill_value=0)
        return jax.lax.psum(g, self.axis)

    def scatter_rows(self, pool, rows, vals):
        return pool.at[self._local(pool, rows)].set(vals.astype(pool.dtype),
                                                    mode="drop")

    def cmp_attend(self, k_pool, v_pool, rows, q1, blk_ok, rep):
        """Compression attention + selection scores over OWNED rows only.

        Each shard attends the compressed rows it holds (non-owned rows
        masked NEG_INF) and the per-query (m, l, acc) triples are merged
        with a pmax/psum — O(B·Hq·D) on the wire instead of the
        O(B·NB·Hkv·D) all-gather of the row values.  Exact up to fp
        reassociation: every row is owned by exactly one shard, so the
        shard partials partition the key set.  The selection scores ride
        the same local reads (zero-filled non-owned rows psum exactly)."""
        from repro.core.nsa_causal import _cmp_attend_from_rows
        from repro.numerics import NEG_INF, mask_to_bias
        from repro.core.branches import repeat_kv

        if os.environ.get("REPRO_SHARDED_RING_DECODE", "1") == "0":
            return _cmp_attend_from_rows(self.gather(k_pool, rows),
                                         self.gather(v_pool, rows),
                                         q1, blk_ok, rep)
        B, _, Hq, D = q1.shape
        Hkv = k_pool.shape[1]
        li = self._local(k_pool, rows)
        owned = li < k_pool.shape[0]                               # (B, NB)
        kl = k_pool.at[li].get(mode="fill", fill_value=0)          # (B,NB,Hkv,D)
        vl = v_pool.at[li].get(mode="fill", fill_value=0)
        # selection scores: zero-filled non-owned rows contribute 0 → psum
        # reassembles the exact dense q·k row scores
        qg = q1.reshape(B, 1, Hkv, rep, D)
        s = jnp.einsum("bmkrd,bnkd->bkn", qg.astype(jnp.float32),
                       kl.astype(jnp.float32),
                       preferred_element_type=jnp.float32) / (D ** 0.5)
        s = jax.lax.psum(jnp.where(owned[:, None, :], s, 0.0), self.axis)
        s = jnp.where(blk_ok[:, None, :], s, NEG_INF)
        # compression attention: local partial stats, merged across shards
        qh = q1.transpose(0, 2, 1, 3)                              # (B,Hq,1,D)
        bias = mask_to_bias((blk_ok & owned)[:, None, None, :])
        logits = jnp.einsum(
            "bhnd,bhld->bhnl", qh,
            repeat_kv(kl, rep).transpose(0, 2, 1, 3),
            preferred_element_type=jnp.float32) / (D ** 0.5) + bias
        m = logits.max(-1)                                         # (B,Hq,1)
        pw = jnp.exp(logits - m[..., None])
        pw = jnp.where(logits <= NEG_INF / 2, 0.0, pw)
        l = pw.sum(-1)
        acc = jnp.einsum("bhnl,bhld->bhnd", pw,
                         repeat_kv(vl, rep).transpose(0, 2, 1, 3)
                         .astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m, self.axis)
        w = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * w, self.axis)
        acc_g = jax.lax.psum(acc * w[..., None], self.axis)
        out = (acc_g / jnp.maximum(l_g, 1e-20)[..., None]).astype(v_pool.dtype)
        return out, s


def sharded_paged_decode(backend, params, q1, k1, v1, cache, table,
                         lengths, *, cfg, page, x1=None):
    """One paged NSA decode step with KV pools partitioned across the mesh.

    Called from ``core.nsa_causal.nsa_causal_decode_paged`` when the
    resolved backend is sharded.  The whole step runs under one
    ``shard_map``: pools enter/leave row-sharded (``P(axis)``), everything
    else (query, table, lengths, params) is replicated, and the attention
    output is identical on every shard (gathers psum; the compression
    branch merges softmax stats instead — see ``_ShardedPoolOps``).
    Requires the pool row counts R and Rc to divide the mesh axis;
    otherwise falls back to the dense single-device pool ops under the
    inner backend.
    """
    from repro.core import nsa_causal
    from repro.core.backend import get_paged_gather

    mesh, axis = backend._require_mesh("paged decode")
    inner = backend._resolve_inner()
    p = mesh.shape[axis]
    R, Rc = cache["k"].shape[0], cache["k_cmp"].shape[0]
    if p == 1 or R % p or Rc % p:
        if p > 1:
            _warn_once("paged decode", "pool-rows-indivisible",
                       f"pool rows R={R}/Rc={Rc} not divisible by "
                       f"{axis!r}={p}")
        ops = nsa_causal._DensePoolOps(get_paged_gather(inner))
        return nsa_causal.nsa_causal_decode_paged(
            params, q1, k1, v1, cache, table, lengths, cfg=cfg, page=page,
            x1=x1, _pool_ops=ops)

    pool_ops = _ShardedPoolOps(axis)
    pool_spec = {name: P(axis) for name in cache}

    def body(params, q1, k1, v1, cache, table, lengths, x1):
        return nsa_causal.nsa_causal_decode_paged(
            params, q1, k1, v1, cache, table, lengths, cfg=cfg, page=page,
            x1=x1, _pool_ops=pool_ops)

    args = [(params, P()), (q1, P()), (k1, P()), (v1, P()),
            (cache, pool_spec), (table, P()), (lengths, P()), (x1, P())]
    arrs = [a for a, _ in args if a is not None]
    specs = tuple(s for a, s in args if a is not None)
    present = [a is not None for a, _ in args]

    def wrapper(*xs):
        it = iter(xs)
        return body(*[next(it) if pr else None for pr in present])

    return shard_map(wrapper, mesh=mesh, in_specs=specs,
                     out_specs=(P(), pool_spec), check_vma=False)(*arrs)


if "sharded" not in list_backends():       # idempotent on re-import paths
    register_backend("sharded", ShardedBackend())
