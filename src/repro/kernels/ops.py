"""jit'd wrappers: layout transforms between core tensor convention
(B, N, H, D) and the kernels' GQA-grouped (B·Hkv, rep, N, D) / blocked
layouts.

These are the entry points the "pallas" / "interpret" attention backends
(``repro.core.backend.PallasBackend``) dispatch to.

Shape/dtype contract (shared by all four attention wrappers):

  * q is (B, N, Hq, D); k, v are (B, L, Hkv, D) with Hq = Hkv·rep.  The
    kernels are GQA-NATIVE: K/V are NEVER head-repeated — each kernel's grid
    iterates KV heads and a group's ``rep`` query heads share one fetched
    K/V tile, folded into the matmul row dimension (forward and fused
    backward; dK/dV accumulate across the group inside the contraction).
    Query head h·rep + r belongs to KV head h (the ``branches.repeat_kv``
    convention, kept so the jnp reference pins semantics).
  * ``mask`` / ``key_valid`` is a (B, L) bool array, True = real token.
    It masks KEYS only.  Internally the mask becomes an additive fp32 key
    bias (0 valid / NEG_INF = −1e30 padding) applied in LOGIT space, which
    is also exactly what the fused backward kernels recompute — so masked
    keys receive exactly zero gradient.  A query row whose keys are ALL
    masked returns zeros.
  * ``q_valid`` (where accepted) is an OPTIMIZATION-ONLY hint: rows whose
    queries are padding produce UNSPECIFIED values (the kernels may skip
    whole dead q-tiles, leaving zeros; the jnp backend ignores the hint) —
    the model masks padded rows at the combine epilogue either way.
  * Any floating dtype is accepted (fp32 and bf16 are tested); softmax
    statistics are always fp32 inside the kernels.  The matmul-OPERAND
    dtype follows the kernel precision contract
    (``common.resolve_compute_dtype``): bf16 inputs keep bf16 tiles through
    QK^T and PV with fp32 accumulation; REPRO_FP8=1 opts QK^T into fp8.
  * TILE-OCCUPANCY SKIPPING (``kernels/occupancy.py``): every wrapper
    precomputes per-tile liveness from its mask / causal structure /
    offsets, ships it to the kernel as a scalar-prefetch operand, and
    reports it to ``occupancy.record`` so ``perf_iter.py --occupancy`` can
    audit the live/total tile ratio.

Tiles and padding: ``flash_attention`` resolves its (tq, tk) tiles through
``kernels/tuning.py`` (cache → autotune → deterministic heuristic) and PADS
the query/key axes up to tile multiples — padded keys carry NEG_INF bias
(zero weight, zero gradient), padded query rows are sliced off — so ragged
lengths with no friendly divisor no longer collapse the tile size to 1.

Batched (ragged) geometries: every wrapper carries a leading batch dim, so a
packed batch of variable-size samples — one mask row per sample, produced by
``repro.core.balltree.pack_ragged`` — is a single kernel launch.

All wrappers are differentiable in their floating inputs: the kernel calls
carry ``jax.custom_vjp`` fused backward passes (see each kernel module), and
the layout transforms here are plain jnp ops, so ``jax.grad`` through
``bsa_attention`` / ``nsa_causal_attention`` works on the kernel backends.
Mask-derived biases are non-differentiable by construction (their cotangent
is dropped in the kernel VJPs).  Every wrapper takes ``interpret`` (None =
auto-detect, True = force Pallas interpret mode — the "interpret" backend).

``gated_combine`` is the fifth op: the fused branch-combination epilogue
(see ``kernels/epilogue.py``), differentiable in branch outputs and gates.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import occupancy, tuning
from repro.kernels.bta import ball_attention_kernel_call
from repro.kernels.common import resolve_compute_dtype
from repro.kernels.epilogue import gated_combine_kernel_call
from repro.kernels.flash import flash_attention_kernel_call
from repro.kernels.local import local_window_kernel_call
from repro.kernels.selection import selection_attention_kernel_call
from repro.kernels.varlen import flash_attention_varlen_kernel_call
from repro.numerics import (NEG_INF, key_padding_bias,
                            segment_ids_from_offsets)

__all__ = ["ball_attention", "flash_attention", "local_window_attention",
           "selection_attention", "gated_combine",
           "ball_attention_varlen", "flash_attention_varlen",
           "local_window_attention_varlen", "selection_attention_varlen"]


def _to_bh(t):
    """(B, L, Hkv, D) → (B·Hkv, L, D) — the single-K/V-stream-per-head layout."""
    B, L, H, D = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B * H, L, D)


def _to_grouped(q, Hkv):
    """(B, N, Hq, D) → (B·Hkv, rep, N, D): query head h·rep + r rides KV
    head h's grid cells as fused matmul rows (GQA-native kernel layout)."""
    B, N, Hq, D = q.shape
    rep = Hq // Hkv
    return (q.reshape(B, N, Hkv, rep, D)
             .transpose(0, 2, 3, 1, 4)
             .reshape(B * Hkv, rep, N, D))


def _from_grouped(o, B, Hkv):
    BH, rep, N, D = o.shape
    return (o.reshape(B, Hkv, rep, N, D)
             .transpose(0, 3, 1, 2, 4)
             .reshape(B, N, Hkv * rep, D))


def ball_attention(q, k, v, mask, ball_size: int, *,
                   interpret: bool | None = None):
    """Ball-Tree Attention: full attention inside each contiguous ball.

    q: (B, N, Hq, D); k, v: (B, N, Hkv, D) with Hq = Hkv·rep — GQA-native,
    no KV repetition; ``mask``: (B, N) bool (True = real) or None — masks
    keys in logit space, one row per sample of a packed ragged batch.
    ``ball_size`` must divide N.  ``interpret`` forces Pallas interpret mode
    (None = auto-detect).  Returns (B, N, Hq, D).  Differentiable in q, k, v.
    """
    B, N, Hq, D = q.shape
    Hkv = k.shape[2]
    kb = key_padding_bias(mask, B, N)
    occupancy.record("bta", occupancy.key_tile_live(kb, ball_size))
    out = ball_attention_kernel_call(
        _to_grouped(q, Hkv), _to_bh(k), _to_bh(v), kb,
        ball_size=ball_size, n_heads=Hkv, interpret=interpret,
        compute=resolve_compute_dtype(q.dtype))
    return _from_grouped(out, B, Hkv)


def flash_attention(q, k, v, *, key_valid=None, causal=False,
                    block_causal=False, ell=1, bias=None, q_valid=None,
                    tq: int | None = None, tk: int | None = None,
                    interpret: bool | None = None):
    """Streaming-softmax attention of q vs an arbitrary-length K/V.

    q: (B, N, Hq, D); k, v: (B, L, Hkv, D) with Hq = Hkv·rep (GQA-native; L
    may differ from N — the compression branch attends N queries to L = N/ℓ
    coarse tokens).

    ``key_valid``: (B, L) bool, True = real key (per-sample row of a packed
    ragged batch).  ``causal``: token-level lower-triangular mask (needs
    L == N).  ``block_causal``: coarse-block causality with block length
    ``ell`` — query t sees coarse key j iff (j+1)·ell − 1 < t; the mask is
    generated in-kernel from indices and never materialised.  ``bias``:
    (B, 1, 1, L) fp32 additive key bias accepted as an alternative to
    ``key_valid`` (the two add if both given).  ``tq``/``tk`` override the
    tile sizes; left as None they resolve through the ``kernels/tuning.py``
    autotuner (cache → measure → heuristic).  Axes that are not tile
    multiples are PADDED (masked keys / sliced query rows), never shrunk to
    degenerate tiles.  Returns (B, N, Hq, D).  Differentiable in q, k, v."""
    B, N, Hq, D = q.shape
    Hkv = k.shape[2]
    L = k.shape[1]
    if interpret is None:
        from repro.kernels.common import should_interpret
        interpret = should_interpret()
    compute = resolve_compute_dtype(q.dtype)
    if tq is None or tk is None:
        atq, atk = tuning.get_tiles(
            "flash", n_q=N, n_k=L, d=D, dtype=q.dtype, interpret=interpret,
            variant=tuning.flash_variant(causal, block_causal, ell),
            compute=compute,
            measure=_flash_measure(N, L, D, q.dtype, causal, block_causal,
                                   ell, interpret))
        tq = tq or atq
        tk = tk or atk
    tq = tuning.clamp_tile(tq, N, interpret=interpret)
    tk = tuning.clamp_tile(tk, L, interpret=interpret)

    kb = key_padding_bias(key_valid, B, L)
    if bias is not None:
        kb = kb + bias.reshape(B, L).astype(jnp.float32)

    # pad axes to tile multiples: padded keys get NEG_INF bias (zero weight,
    # zero grad), padded query rows compute garbage and are sliced off
    Np, Lp = tuning.round_up(N, tq), tuning.round_up(L, tk)
    if Lp != L:
        k = jnp.pad(k, ((0, 0), (0, Lp - L), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Lp - L), (0, 0), (0, 0)))
        kb = jnp.pad(kb, ((0, 0), (0, Lp - L)), constant_values=NEG_INF)
    if Np != N:
        q = jnp.pad(q, ((0, 0), (0, Np - N), (0, 0), (0, 0)))
        if q_valid is not None:
            q_valid = jnp.pad(q_valid, ((0, 0), (0, Np - N)))

    live = occupancy.flash_live_map(kb, tq, tk, Np // tq, q_valid=q_valid,
                                    causal=causal, block_causal=block_causal,
                                    ell=ell)
    occupancy.record("flash", live)
    out = flash_attention_kernel_call(
        _to_grouped(q, Hkv), _to_bh(k), _to_bh(v), kb, live, n_heads=Hkv,
        causal=causal, block_causal=block_causal, ell=ell, tq=tq, tk=tk,
        interpret=interpret, compute=compute)
    out = _from_grouped(out, B, Hkv)
    return out[:, :N] if Np != N else out


def _flash_measure(N, L, D, dtype, causal, block_causal, ell, interpret):
    """Measure callback for the tuner — only invoked on a cache miss with
    autotuning enabled (``tuning.get_tiles`` owns that policy)."""
    if not tuning.autotune_enabled():
        return None

    def measure(tq, tk):
        from repro.kernels.tuning import tune_measure_flash
        return tune_measure_flash(tq, tk, n_q=N, n_k=L, d=D, dtype=dtype,
                                  causal=causal, block_causal=block_causal,
                                  ell=ell, interpret=interpret)
    return measure


def local_window_attention(q, k, v, window: int, mask=None, *,
                           interpret: bool | None = None):
    """Blocked local causal attention (the LM 'ball' branch).

    q: (B, N, Hq, D); k, v: (B, N, Hkv, D) with Hq = Hkv·rep (GQA-native);
    query block i (size ``window``) attends causally within itself and fully
    to block i−1.  ``mask``: (B, N) bool (True = real) or None — key-validity
    for packed ragged batches, applied in logit space inside the kernel.
    Returns (B, N, Hq, D).  Differentiable in q, k, v."""
    B, N, Hq, D = q.shape
    Hkv = k.shape[2]
    kb = key_padding_bias(mask, B, N)
    occupancy.record("local", _local_half_live(kb, window))
    out = local_window_kernel_call(
        _to_grouped(q, Hkv), _to_bh(k), _to_bh(v), kb,
        window=window, n_heads=Hkv, interpret=interpret,
        compute=resolve_compute_dtype(q.dtype))
    return _from_grouped(out, B, Hkv)


def _local_half_live(key_bias, window, blk_seg=None):
    """(B, n_b, 2) bool — the two ``pl.when`` half-steps of each local grid
    cell (prev half, self half), exactly what ``kernels/local.py`` skips."""
    kv = occupancy.key_tile_live(key_bias, window)            # (B, n_b)
    self_live = kv
    prev_live = jnp.pad(kv[:, :-1], ((0, 0), (1, 0)))         # block 0: none
    if blk_seg is not None:
        same = jnp.pad(blk_seg[:, 1:] == blk_seg[:, :-1], ((0, 0), (1, 0)))
        prev_live = prev_live & same
    return jnp.stack([prev_live, self_live], axis=-1)


def selection_attention(q, k, v, top_idx, sel_valid, mask, *,
                        block_size: int, group_size: int,
                        interpret: bool | None = None, q_valid=None):
    """Group-selected sparse attention via the scalar-prefetch kernel.

    q: (B, N, Hq, D); k, v: (B, L, Hkv, D) with Hq = Hkv·rep (GQA-native
    from day one: all rep query heads of a group share one fetched block
    set, which is the point of group selection).  L may exceed N — the
    kernel grid is independent in G and NB, so a context-parallel shard can
    pass its local query slab against the full gathered key set.
    ``top_idx``/``sel_valid``: (B, G, Hkv, k*) — per query group and KV head,
    the selected coarse-block ids and their validity (invalid selections are
    encoded as index −1 for the kernel and skipped).  ``mask``: (B, L) bool
    or None — token validity of the GATHERED keys (padding inside a selected
    block is masked in logit space).  ``q_valid``: (B, N) bool or None —
    query-side validity when it differs from the key mask (sharded callers);
    defaults to ``mask`` under the classic N == L layout.  ``block_size``
    ℓ is the KV block length; ``group_size`` g = N/G tokens per query group.
    Returns (B, N, Hq, D).  Differentiable in q, k, v (dK/dV are
    summed back through the gathered indices, duplicates included)."""
    B, N, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    ell = block_size
    nb = k.shape[1] // ell
    G = top_idx.shape[1]
    g = N // G

    qg = (q.reshape(B, G, g, Hkv, rep, D)
           .transpose(0, 3, 1, 2, 4, 5)
           .reshape(B, Hkv, G, g * rep, D))
    kb = k.reshape(B, nb, ell, Hkv, D).transpose(0, 3, 1, 2, 4)   # (B,Hkv,NB,ℓ,D)
    vb = v.reshape(B, nb, ell, Hkv, D).transpose(0, 3, 1, 2, 4)
    sel_valid = occupancy.invalidate_dead_groups(
        sel_valid, q_valid if q_valid is not None else mask, N)
    idx = jnp.where(sel_valid, top_idx, -1).astype(jnp.int32)
    idx = idx.transpose(0, 2, 1, 3)                               # (B,Hkv,G,k*)
    if mask is None:
        tok_bias = jnp.zeros((B, nb, ell), jnp.float32)
    else:
        tok_bias = jnp.where(mask.reshape(B, nb, ell), 0.0, NEG_INF).astype(jnp.float32)

    occupancy.record("selection", idx >= 0)
    out = selection_attention_kernel_call(qg, kb, vb, idx, tok_bias,
                                          interpret=interpret,
                                          compute=resolve_compute_dtype(q.dtype))
    return (out.reshape(B, Hkv, G, g, rep, D)
               .transpose(0, 2, 3, 1, 4, 5)
               .reshape(B, N, Hq, D))


# ---------------------------------------------------------------------------
# Packed-varlen wrappers (the offsets layout — see docs/varlen.md)
#
# Shared contract: NO batch dim.  All samples are concatenated on one packed
# axis (``core.balltree.pack_varlen``): q (T, Hq, D), k/v (L, Hkv, D), with
# ``offsets`` (S+1,) int32 marking per-sample boundaries — every entry a
# multiple of the structural granule (ball size), trailing repeats = empty
# segments.  ``mask`` / ``key_valid`` is the packed (T,)/(L,) bool validity.
# Sample isolation comes from in-kernel segment-id masking plus tile
# skipping (``kernels/varlen.py``), or from the structural guarantee that
# balls / blocks never straddle an offsets boundary.
# ---------------------------------------------------------------------------

def flash_attention_varlen(q, k, v, q_offsets, k_offsets, *, key_valid=None,
                           tq: int | None = None, tk: int | None = None,
                           interpret: bool | None = None):
    """Packed-varlen streaming-softmax attention (the cu_seqlens idiom).

    q: (T, Hq, D) packed queries; k, v: (L, Hkv, D) packed keys/values with
    Hq = Hkv·rep (GQA-native).  ``q_offsets`` (S+1,) / ``k_offsets`` (S+1,)
    int32 mark the per-sample boundaries of the two axes — segment i of the
    queries attends ONLY segment i of the keys (the compression branch
    passes ``k_offsets = q_offsets // ell`` for its pooled key axis).
    ``key_valid``: (L,) bool, True = real key.  Derives per-position segment
    ids and per-tile segment ranges, pads both axes to tile multiples
    (padded keys: NEG_INF bias; padded/capacity query rows attend nothing
    real and are sliced/zeroed), and launches the tile-skipping varlen
    kernel — cross-sample tiles are skipped entirely, so work scales with
    Σ nᵢ² per sample instead of T².  Tiles resolve through
    ``kernels/tuning.py`` under the ``varlen`` layout key (never shared with
    padded-bucket entries).  Returns (T, Hq, D).  Differentiable in q, k, v.
    """
    T, Hq, D = q.shape
    L, Hkv, _ = k.shape
    if interpret is None:
        from repro.kernels.common import should_interpret
        interpret = should_interpret()
    compute = resolve_compute_dtype(q.dtype)
    if tq is None or tk is None:
        atq, atk = tuning.get_tiles(
            "flash", n_q=T, n_k=L, d=D, dtype=q.dtype, interpret=interpret,
            variant="plain", layout="varlen", compute=compute)
        tq = tq or atq
        tk = tk or atk
    tq = tuning.clamp_tile(tq, T, interpret=interpret)
    tk = tuning.clamp_tile(tk, L, interpret=interpret)

    kb = key_padding_bias(key_valid[None] if key_valid is not None else None,
                          1, L)
    Tp, Lp = tuning.round_up(T, tq), tuning.round_up(L, tk)
    if Lp != L:
        k = jnp.pad(k, ((0, Lp - L), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, Lp - L), (0, 0), (0, 0)))
        kb = jnp.pad(kb, ((0, 0), (0, Lp - L)), constant_values=NEG_INF)
    if Tp != T:
        q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))

    # positions at/after offsets[-1] (capacity + tile padding) get segment id
    # S, which matches no real sample — padded queries and keys are mutually
    # invisible to real ones by the in-kernel equality test.  Concrete
    # offsets resolve through the host-side LRU (one build per batch layout
    # instead of one per call); tracers take the jnp path inside
    qseg, kseg, qrng, krng = occupancy.cached_varlen_maps(
        q_offsets, k_offsets, Tp, Lp, tq, tk)
    occupancy.record("varlen_flash", occupancy.ranges_live_map(qrng, krng))

    out = flash_attention_varlen_kernel_call(
        _to_grouped(q[None], Hkv), _to_bh(k[None]), _to_bh(v[None]), kb,
        qseg[None], kseg[None], qrng, krng,
        tq=tq, tk=tk, interpret=interpret, compute=compute)
    out = _from_grouped(out, 1, Hkv)[0]
    return out[:T] if Tp != T else out


def ball_attention_varlen(q, k, v, offsets, mask, ball_size: int, *,
                          interpret: bool | None = None):
    """Packed-varlen Ball-Tree Attention.

    q: (T, Hq, D); k, v: (T, Hkv, D); ``offsets`` (S+1,) int32 per the
    packed contract; ``mask``: (T,) bool or None.  Because every offsets
    entry is a multiple of ``ball_size`` (``pack_varlen`` guarantees it), no
    ball straddles a sample boundary — the block-diagonal BTA kernel on the
    packed axis is already sample-isolating, so this dispatches to the
    batched kernel at B=1 with zero per-sample padding slots.  Capacity-tail
    balls are fully masked and return zeros.  Returns (T, Hq, D).
    Differentiable in q, k, v."""
    return ball_attention(q[None], k[None], v[None],
                          mask[None] if mask is not None else None,
                          ball_size, interpret=interpret)[0]


def local_window_attention_varlen(q, k, v, offsets, window: int, mask=None, *,
                                  interpret: bool | None = None):
    """Packed-varlen blocked local causal attention.

    q: (T, Hq, D); k, v: (T, Hkv, D); ``offsets`` (S+1,) int32 — every entry
    must be a multiple of ``window`` so blocks never straddle a boundary
    (``pack_varlen`` with a ball-size multiple of the window guarantees it).
    Per-BLOCK segment ids derived from ``offsets`` ride into the kernel: the
    first block of each sample sees no prev block, and a sample's last block
    leaks no gradient to the next sample (``kernels/local.py``).  ``mask``:
    (T,) bool or None.  Returns (T, Hq, D).  Differentiable in q, k, v."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    seg = segment_ids_from_offsets(offsets, T)
    blk_seg = seg.reshape(T // window, window)[:, 0][None]     # (1, n_b)
    kb = key_padding_bias(mask[None] if mask is not None else None, 1, T)
    occupancy.record("local", _local_half_live(kb, window, blk_seg))
    out = local_window_kernel_call(
        _to_grouped(q[None], Hkv), _to_bh(k[None]), _to_bh(v[None]), kb,
        window=window, n_heads=Hkv, interpret=interpret, blk_seg=blk_seg,
        compute=resolve_compute_dtype(q.dtype))
    return _from_grouped(out, 1, Hkv)[0]


def selection_attention_varlen(q, k, v, top_idx, sel_valid, offsets, mask, *,
                               block_size: int, group_size: int,
                               interpret: bool | None = None):
    """Packed-varlen group-selected sparse attention.

    q: (T, Hq, D); k, v: (T, Hkv, D); ``top_idx``/``sel_valid``:
    (G, Hkv, k*) — selected coarse-block ids are GLOBAL packed-axis block
    indices.  Sample isolation is enforced UPSTREAM: the selection scores
    mask cross-sample (group, block) pairs to NEG_INF
    (``core.bsa._selection_scores`` with segment ids), so a selected block
    always belongs to the query group's own sample and the gather kernel
    needs no extra masking — ``offsets`` is part of the signature for
    contract uniformity (and future in-kernel verification).  ``mask``:
    (T,) bool or None masks tokens inside gathered blocks.  Returns
    (T, Hq, D).  Differentiable in q, k, v."""
    return selection_attention(
        q[None], k[None], v[None], top_idx[None], sel_valid[None],
        mask[None] if mask is not None else None,
        block_size=block_size, group_size=group_size, interpret=interpret)[0]


def paged_gather(pool, rows, *, interpret: bool | None = None,
                 force_kernel: bool = False):
    """Gather pool rows for the paged decode path.

    ``pool``: (R, Hkv, D) flat KV pool; ``rows``: int32 of any shape holding
    pool-row indices.  Returns ``rows.shape + (Hkv, D)``.

    Compiled TPU runs use the scalar-prefetch row-DMA kernel
    (``kernels/paged.py``).  Interpret mode falls back to plain advanced
    indexing UNLESS ``force_kernel``: the kernel is one grid cell per row,
    which Mosaic pipelines on hardware but the interpreter executes as
    O(rows) Python per decode step — the fallback keeps the interpret CI leg
    linear (same reasoning as ``common.interpret_batch_map``), and the
    forced path lets parity tests still execute the kernel body.
    """
    if interpret is None:
        from repro.kernels.common import should_interpret
        interpret = should_interpret()
    if interpret and not force_kernel:
        return pool[rows]
    from repro.kernels.paged import paged_gather_kernel_call
    flat = paged_gather_kernel_call(pool, rows.reshape(-1).astype(jnp.int32),
                                    interpret=interpret)
    return flat.reshape(*rows.shape, *pool.shape[1:])


def gated_combine(outs, gates, mask, *, interpret: bool | None = None):
    """Fused gate-and-mask epilogue over the three branch outputs.

    ``outs``: three (B, N, H, D) arrays (same shape/dtype); ``gates``: three
    fp32 arrays broadcastable to (B, N, H, 1) — scalar-mode (1, 1, H, 1) or
    token-mode (B, N, H, 1) sigmoid gate values; ``mask``: (B, N) bool
    (True = real query) or None.  Computes
    ``(Σ_b g_b · out_b) · mask`` in one Pallas pass instead of three fp32
    HBM temporaries.  Returns (B, N, H, D) in ``outs[0].dtype``.
    Differentiable in outs and gates (gate cotangents flow back through the
    jnp broadcast, so scalar gates receive their summed gradient)."""
    o1, o2, o3 = outs
    B, N, H, D = o1.shape
    R = B * N * H
    g1, g2, g3 = (jnp.broadcast_to(g.astype(jnp.float32), (B, N, H, 1))
                  .reshape(R, 1) for g in gates)
    if mask is None:
        m = jnp.ones((R, 1), jnp.float32)
    else:
        m = (jnp.broadcast_to(mask[:, :, None], (B, N, H))
             .reshape(R, 1).astype(jnp.float32))
    rows = [o.reshape(R, D) for o in (o1, o2, o3)]

    tile = tuning.heuristic_tile(R, 1024)
    Rp = tuning.round_up(R, tile)
    if Rp != R:
        pad = ((0, Rp - R), (0, 0))
        rows = [jnp.pad(o, pad) for o in rows]
        g1, g2, g3 = (jnp.pad(g, pad) for g in (g1, g2, g3))
        m = jnp.pad(m, pad)
    out = gated_combine_kernel_call(rows[0], rows[1], rows[2], g1, g2, g3, m,
                                    tile=tile, interpret=interpret)
    if Rp != R:
        out = out[:R]
    return out.reshape(B, N, H, D)
