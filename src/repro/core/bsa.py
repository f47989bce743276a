"""Ball Sparse Attention (BSA) — the paper's contribution, non-causal form.

Operates on ball-ordered point sequences (see ``core/balltree.py``): after the
ball-tree permutation, every contiguous chunk of ``ball_size`` tokens is a
spatially compact ball.  Three branches (paper Eq. 9):

  * ``ball`` — Ball-Tree Attention: full attention inside each ball,
  * ``cmp``  — compression: queries attend to φ-pooled coarse KV blocks,
  * ``slc``  — selection: per query *group*, top-k coarse blocks are fetched
               at token resolution and attended exactly,

combined with sigmoid gates.  Group selection (Eq. 10–12), query-coarsened
scoring (Eq. 13–14), group compression (Eq. 15) and own-ball masking (§3.2)
are all implemented and switchable via :class:`repro.core.config.BSAConfig`.

All functions are shape-polymorphic over GQA: q has ``Hq = Hkv * rep`` heads.

Every device op of a BSA call runs under ``jax.named_scope("bsa")`` and one
branch scope: ``ball``, ``compression``, ``selection`` (``score``, ``topk``,
``attend``) or ``combine``.  Scopes name the ops in the profiler's trace
(HLO ``op_name`` metadata) and leave instruction and kernel names alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.backend import (
    accepts_kwarg,
    get_combine,
    get_varlen,
    resolve_branch_backends,
)
from repro.core.branches import (
    NEG_INF,
    block_validity,
    diag_scores,
    gate_values,
    gates_init,
    mask_to_bias,
    phi_apply,
    phi_init,
    score_dtype_cast,
    sdpa,
)
from repro.core.config import BSAConfig
from repro.distributed.sharding import constrain
from repro.numerics import segment_ids_from_offsets

__all__ = ["bsa_init", "bsa_attention", "bsa_attention_varlen",
           "ball_attention_ref"]


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def bsa_init(key, cfg: BSAConfig, *, n_heads: int, n_kv_heads: int, head_dim: int,
             d_model: int, param_dtype=jnp.float32) -> dict:
    kk, kv, kq, kg = jax.random.split(key, 4)
    params = {
        "phi_k": phi_init(kk, cfg, head_dim, param_dtype=param_dtype),
        "phi_v": phi_init(kv, cfg, head_dim, param_dtype=param_dtype),
        "gates": gates_init(kg, cfg, n_heads, d_model, param_dtype=param_dtype),
    }
    if cfg.query_cmp_selection or cfg.group_compression:
        params["phi_q"] = phi_init(kq, cfg, head_dim, param_dtype=param_dtype)
    return params


# ---------------------------------------------------------------------------
# Branch 1 — Ball-Tree Attention (block-diagonal)
# ---------------------------------------------------------------------------

def ball_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       mask: jnp.ndarray | None, ball_size: int,
                       chunk_balls: int = 0) -> jnp.ndarray:
    """Full attention within each contiguous ball.  Pure-jnp reference.
    ``chunk_balls`` > 0 processes balls in lax.map tiles (memory bound)."""
    B, N, H, D = q.shape
    m = ball_size
    assert N % m == 0, f"N={N} not a multiple of ball_size={m}"
    nb = N // m
    qb = q.reshape(B, nb, m, H, D).transpose(0, 1, 3, 2, 4)      # (B,nb,H,m,D)
    kb = k.reshape(B, nb, m, H, D).transpose(0, 1, 3, 2, 4)
    vb = v.reshape(B, nb, m, H, D).transpose(0, 1, 3, 2, 4)
    mb = mask.reshape(B, nb, 1, 1, m) if mask is not None else None

    def attend(qc, kc, vc, mc):
        return sdpa(qc, kc, vc, mask_to_bias(mc) if mc is not None else None)

    if chunk_balls and nb % chunk_balls == 0 and nb > chunk_balls:
        nc = nb // chunk_balls
        resh = lambda t: t.reshape(B, nc, chunk_balls, *t.shape[2:]).transpose(
            1, 0, *range(2, t.ndim + 1))
        if mb is not None:
            out = jax.lax.map(jax.checkpoint(lambda t: attend(t[0], t[1], t[2], t[3])),
                              (resh(qb), resh(kb), resh(vb), resh(mb)))
        else:
            out = jax.lax.map(jax.checkpoint(lambda t: attend(t[0], t[1], t[2], None)),
                              (resh(qb), resh(kb), resh(vb)))
        out = out.transpose(1, 0, *range(2, out.ndim)).reshape(B, nb, H, m, D)
    else:
        out = attend(qb, kb, vb, mb)                              # (B,nb,H,m,D)
    return out.transpose(0, 1, 3, 2, 4).reshape(B, N, H, D)


def _ball_branch(q, k, v, mask, cfg: BSAConfig, backend):
    # GQA-native: K/V go in un-repeated — the backend owns the group
    # strategy (kernels share one fetch per group, jnp repeats internally)
    return backend.ball(q, k, v, mask, ball_size=cfg.ball_size,
                        chunk_tokens=cfg.jnp_chunk_tokens)


# ---------------------------------------------------------------------------
# Branch 2 — Compression
# ---------------------------------------------------------------------------

def _compression_branch(params, q, k, v, mask, cfg: BSAConfig, backend):
    """Returns (out, k_cmp, v_cmp, blk_valid). out: (B, N, Hq, D)."""
    B, N, Hq, D = q.shape
    k_cmp = phi_apply(params["phi_k"], k, mask, cfg)              # (B,NB,Hkv,D)
    v_cmp = phi_apply(params["phi_v"], v, mask, cfg)
    blk_valid = block_validity(mask, B, N, cfg.cmp_block)          # (B,NB)
    # GQA-native: the coarse K/V stay at Hkv heads — no repeat_kv blowup

    # q_valid is an OPTIMIZATION HINT: rows it marks invalid are masked by
    # the combine epilogue anyway, so kernels may skip whole dead query
    # tiles.  Probed by signature so third-party backends keep working.
    hint = mask is not None and accepts_kwarg(backend.flash, "q_valid")

    if cfg.group_compression:
        # Eq. 15: pool queries too; attend at block level; un-pool ℓ× via a
        # broadcast VIEW (jnp.repeat would materialise the ℓ-fold copy)
        nb = N // cfg.cmp_block
        q_cmp = phi_apply(params["phi_q"], q, mask, cfg)           # (B,NB,Hq,D)
        kw = {"q_valid": blk_valid} if hint else {}
        out_c = backend.flash(q_cmp, k_cmp, v_cmp, key_valid=blk_valid,
                              chunk_tokens=cfg.jnp_chunk_tokens,
                              **kw)                                # (B,NB,Hq,D)
        out = jnp.broadcast_to(out_c[:, :, None],
                               (B, nb, cfg.cmp_block, Hq, D)
                               ).reshape(B, N, Hq, D)
        return out, k_cmp, v_cmp, blk_valid

    kw = {"q_valid": mask} if hint else {}
    out = backend.flash(q, k_cmp, v_cmp, key_valid=blk_valid,
                        chunk_tokens=cfg.jnp_chunk_tokens, **kw)
    return out, k_cmp, v_cmp, blk_valid


# ---------------------------------------------------------------------------
# Branch 3 — Selection
# ---------------------------------------------------------------------------

def _selection_scores(params, q, k_cmp, blk_valid, mask, cfg: BSAConfig,
                      q_seg=None):
    """Group-level importance scores.

    Returns (scores, n_groups, rows_are_blocks):
      scores: (B, G, Hkv, NB) fp32, already masked (invalid block / own ball).

    ``q_seg``: (N,) int32 per-token segment ids for a packed-varlen axis
    (shared across the batch dim, which is 1 there) — candidate blocks of
    OTHER segments are scored NEG_INF, so top-k never selects across a
    sample boundary and ``sel_valid`` goes False for any that slip in.
    """
    B, N, Hq, D = q.shape
    Hkv = k_cmp.shape[2]
    rep = Hq // Hkv
    nb = k_cmp.shape[1]
    ell = cfg.cmp_block
    g = cfg.group_size if cfg.group_size else 1

    if cfg.query_cmp_selection and cfg.group_size:
        # Eq. 13–14: score with φ-pooled queries (block granularity);
        # q-heads within each GQA group are summed (NSA: shared fetch per group)
        q_s = phi_apply(params["phi_q"], q, mask, cfg)             # (B,NB,Hq,D)
        s = diag_scores(q_s, k_cmp, rep, cfg.score_dtype)           # (B,NB,Hkv,NB)
        rows_per_group = max(g // ell, 1)
        G = nb // rows_per_group
        s = s.reshape(B, G, rows_per_group, Hkv, nb).mean(axis=2)   # Eq. 12 mean
    else:
        # token-level scores; optional group averaging (Eq. 10–12)
        s = diag_scores(q, k_cmp, rep, cfg.score_dtype)             # (B,N,Hkv,NB)
        if cfg.group_size:
            G = N // g
            s = s.reshape(B, G, g, k_cmp.shape[2], nb).mean(axis=2)
        else:
            G = N
    s = s / (D ** 0.5)

    # mask invalid blocks
    s = jnp.where(blk_valid[:, None, None, :], s, NEG_INF)
    if cfg.mask_own_ball:
        tokens_per_group = N // s.shape[1]
        grp_ball = (jnp.arange(s.shape[1]) * tokens_per_group) // cfg.ball_size
        blk_ball = (jnp.arange(nb) * ell) // cfg.ball_size
        own = grp_ball[:, None] == blk_ball[None, :]                # (G,NB)
        s = jnp.where(own[None, :, None, :], NEG_INF, s)
    if q_seg is not None:
        # packed-varlen: a group may only select blocks of its own segment.
        # Offsets are ball_size multiples and groups/blocks subdivide balls,
        # so each group/block is wholly inside one segment — [:, 0] suffices.
        grp_seg = q_seg.reshape(s.shape[1], N // s.shape[1])[:, 0]  # (G,)
        blk_seg = q_seg.reshape(nb, ell)[:, 0]                      # (NB,)
        same = grp_seg[:, None] == blk_seg[None, :]
        s = jnp.where(same[None, :, None, :], s, NEG_INF)
    return s


def _select_blocks(scores, k_star: int, select=None):
    """Top-k block ids of every (group, KV head) row and their validity.

    ``select`` (the ids' shape, −1 = an invalid selection) REPLAYS a
    selection made elsewhere — by another layout or backend, whose rounding
    may break a near-tie the other way: its rows are used instead of this
    call's top-k, except rows whose first id is −1, which keep their own.
    Returns (ids, valid, stats); ``stats`` is empty without ``select``,
    else ``gap``: the largest (own k-th score − lowest replayed score) /
    (1 + |own k-th score|) over replayed rows with k valid candidates —
    ≤ 0 when every replayed set is a top-k of THESE scores, rounding-sized
    at a near-tie, large for a wrong or invalid block — and ``flips``: how
    many of those rows differ, as a set, from this call's own top-k.
    """
    top_vals, top_idx = jax.lax.top_k(scores, k_star)
    if select is None:
        return top_idx, top_vals > NEG_INF / 2, {}
    replay = select[..., :1] >= 0
    idx = jnp.where(replay, jnp.maximum(select, 0), top_idx)
    vals = jnp.where(replay & (select < 0), NEG_INF,
                     jnp.take_along_axis(scores, idx, axis=-1))
    kth = top_vals[..., -1]
    live = replay[..., 0] & (kth > NEG_INF / 2)
    gap = jnp.max(jnp.where(live, (kth - vals.min(-1)) / (1 + jnp.abs(kth)),
                            -jnp.inf))
    differs = jnp.any(jnp.sort(idx, -1) != jnp.sort(top_idx, -1), axis=-1)
    return idx, vals > NEG_INF / 2, {"gap": gap,
                                     "flips": jnp.sum(live & differs)}


def _selection_branch(params, q, k, v, k_cmp, blk_valid, mask, cfg: BSAConfig,
                      backend, select=None):
    """Top-k block gather + exact attention.  Returns (out, indices, stats)
    (``stats``: see :func:`_select_blocks`)."""
    B, N, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    ell = cfg.cmp_block
    nb = N // ell

    with jax.named_scope("score"):
        scores = _selection_scores(params, q, k_cmp, blk_valid, mask,
                                   cfg)                            # (B,G,Hkv,NB)
    G = scores.shape[1]
    g = N // G
    with jax.named_scope("topk"):
        top_idx, sel_valid, stats = _select_blocks(
            scores, min(cfg.top_k, nb), select)                    # (B,G,Hkv,k*)

    with jax.named_scope("attend"):
        out = backend.selection(q, k, v, top_idx, sel_valid, mask,
                                block_size=ell, group_size=g,
                                chunk_tokens=cfg.jnp_chunk_tokens)
    return out, jnp.where(sel_valid, top_idx, -1), stats


# ---------------------------------------------------------------------------
# Full BSA
# ---------------------------------------------------------------------------

def bsa_attention(params: dict, q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  *, cfg: BSAConfig, mask: jnp.ndarray | None = None,
                  x: jnp.ndarray | None = None, return_aux: bool = False,
                  select: jnp.ndarray | None = None):
    """Ball Sparse Attention (paper Eq. 9).

    q: (B, N, Hq, D); k, v: (B, N, Hkv, D); mask: (B, N) bool (True = real).
    Each batch row is an independent (ball-ordered) sample; with per-row
    masks a packed batch of MIXED-SIZE clouds (``core.balltree.pack_ragged``)
    equals running every cloud alone — padded keys are masked in logit space
    on every branch (kernels included), padded query rows are zeroed here.
    ``x`` is the pre-projection layer input, needed only for token gating.
    ``select``: (B, G, Hkv, k*) block ids to replay instead of this call's
    top-k (see :func:`_select_blocks`; its ``gap``/``flips`` join aux).
    Returns (B, N, Hq, D) [+ aux dict; its ``indices`` are the selected
    block ids, −1 where a selection is invalid].
    """
    B, N, Hq, D = q.shape
    assert k.shape[:2] == (B, N) and v.shape == k.shape
    assert Hq % k.shape[2] == 0, "q heads must be a multiple of kv heads"
    with jax.named_scope("bsa"):
        # precision contract: under score_dtype="bfloat16" the branch inputs go
        # in bf16 (kernels keep QK^T/PV operands bf16, accumulate fp32) and the
        # combined output is cast back to the caller's dtype at the end.
        in_dtype = q.dtype
        q, k, v = score_dtype_cast(cfg, q, k, v)

        # logical-axis hints for the sharded backend / GSPMD: no-ops outside an
        # axis_rules context (mesh_context enters one), so single-device runs
        # are untouched; under a mesh the glue between shard_mapped ops keeps
        # the sequence dim on the mesh axis instead of bouncing to replicated
        q = constrain(q, "batch", "seq_sp", None, None)
        k = constrain(k, "batch", "seq_sp", None, None)
        v = constrain(v, "batch", "seq_sp", None, None)

        bk = resolve_branch_backends(cfg)
        with jax.named_scope("ball"):
            out_ball = _ball_branch(q, k, v, mask, cfg, bk["ball"])
        with jax.named_scope("compression"):
            out_cmp, k_cmp, v_cmp, blk_valid = _compression_branch(
                params, q, k, v, mask, cfg, bk["cmp"])
        with jax.named_scope("selection"):
            out_slc, top_idx, stats = _selection_branch(
                params, q, k, v, k_cmp, blk_valid, mask, cfg, bk["slc"], select)

        with jax.named_scope("combine"):
            gates = gate_values(params["gates"], cfg, x, Hq)
            # fused epilogue: gate + sum + query-mask in one pass (the pallas
            # backends run kernels/epilogue.py; others fall back to the jnp ref)
            out = get_combine(bk["ball"])(
                (out_ball, out_cmp, out_slc),
                (gates["ball"], gates["cmp"], gates["slc"]), mask).astype(in_dtype)
            out = constrain(out, "batch", "seq_sp", None, None)
        if return_aux:
            return out, {"ball": out_ball, "cmp": out_cmp, "slc": out_slc,
                         "indices": top_idx, "gates": gates, **stats}
        return out


def bsa_attention_varlen(params: dict, q: jnp.ndarray, k: jnp.ndarray,
                         v: jnp.ndarray, *, cfg: BSAConfig,
                         offsets: jnp.ndarray,
                         mask: jnp.ndarray | None = None,
                         x: jnp.ndarray | None = None,
                         return_aux: bool = False,
                         select: jnp.ndarray | None = None):
    """Ball Sparse Attention over a PACKED-VARLEN batch (``docs/varlen.md``).

    q: (T, Hq, D); k, v: (T, Hkv, D) — all samples concatenated on one
    unbatched token axis of capacity T.  ``offsets``: (S+1,) int32 sample
    boundaries, each a multiple of ``cfg.ball_size`` (what
    ``core.balltree.pack_varlen`` emits); trailing repeats are empty slots
    that keep the shape static under jit.  ``mask``: (T,) bool with True on
    real tokens — pass the one from ``pack_varlen`` so per-sample padding
    and the capacity tail beyond ``offsets[-1]`` are masked (without it the
    tail rows compute garbage; real rows are isolated regardless).

    Semantically identical to running each sample alone (or bucket-padded
    via :func:`bsa_attention`): every branch isolates samples — ball and
    selection structurally (offsets are ball multiples, and a group only
    selects blocks of its own segment), compression and local windows via
    in-kernel segment-id masking — but no padding FLOPs are spent on dummy
    batch slots.  ``x`` is the pre-projection input for token gating, shape
    (T, d_model).  ``select``: (G, Hkv, k*) packed-axis block ids to replay
    (see :func:`bsa_attention`).  Returns (T, Hq, D) [+ aux dict].
    """
    T, Hq, D = q.shape
    assert k.shape[0] == T and v.shape == k.shape
    assert Hq % k.shape[1] == 0, "q heads must be a multiple of kv heads"
    with jax.named_scope("bsa"):
        # precision contract — see bsa_attention
        in_dtype = q.dtype
        q, k, v = score_dtype_cast(cfg, q, k, v)
        ell = cfg.cmp_block
        nb = T // ell
        ct = cfg.jnp_chunk_tokens
        maskb = None if mask is None else mask[None]

        bk = resolve_branch_backends(cfg)
        seg = segment_ids_from_offsets(offsets, T)

        # ball branch — block-diagonal by construction (offsets ∈ ball multiples)
        with jax.named_scope("ball"):
            out_ball = get_varlen(bk["ball"], "ball")(
                q, k, v, offsets, mask, ball_size=cfg.ball_size, chunk_tokens=ct)

        # compression branch — packed tokens vs packed φ-blocks; block offsets
        # are exact because sample boundaries are ball (hence ℓ) multiples
        with jax.named_scope("compression"):
            k_cmp = phi_apply(params["phi_k"], k[None], maskb, cfg)[0]  # (NB,Hkv,D)
            v_cmp = phi_apply(params["phi_v"], v[None], maskb, cfg)[0]
            blk_valid = block_validity(maskb, 1, T, ell)               # (1,NB)
            k_off = offsets // ell
            flash_vl = get_varlen(bk["cmp"], "flash")
            if cfg.group_compression:
                q_cmp = phi_apply(params["phi_q"], q[None], maskb, cfg)[0]
                out_c = flash_vl(q_cmp, k_cmp, v_cmp, k_off, k_off,
                                 key_valid=blk_valid[0], chunk_tokens=ct)  # (NB,Hq,D)
                out_cmp = jnp.broadcast_to(out_c[:, None],
                                           (nb, ell, Hq, D)).reshape(T, Hq, D)
            else:
                out_cmp = flash_vl(q, k_cmp, v_cmp, offsets, k_off,
                                   key_valid=blk_valid[0], chunk_tokens=ct)

        # selection branch — scores get segment isolation on top of the usual
        # validity/own-ball masking, then the gather-attend is layout-agnostic
        with jax.named_scope("selection"):
            with jax.named_scope("score"):
                scores = _selection_scores(params, q[None], k_cmp[None],
                                           blk_valid, maskb, cfg,
                                           q_seg=seg)                  # (1,G,Hkv,NB)
            G = scores.shape[1]
            with jax.named_scope("topk"):
                top_idx, sel_valid, stats = _select_blocks(
                    scores, min(cfg.top_k, nb),
                    None if select is None else select[None])
            with jax.named_scope("attend"):
                out_slc = get_varlen(bk["slc"], "selection")(
                    q, k, v, top_idx[0], sel_valid[0], offsets, mask,
                    block_size=ell, group_size=T // G, chunk_tokens=ct)

        with jax.named_scope("combine"):
            gates = gate_values(params["gates"], cfg,
                                None if x is None else x[None], Hq)
            out = get_combine(bk["ball"])(
                (out_ball[None], out_cmp[None], out_slc[None]),
                (gates["ball"], gates["cmp"], gates["slc"]),
                maskb)[0].astype(in_dtype)
        if return_aux:
            return out, {"ball": out_ball, "cmp": out_cmp, "slc": out_slc,
                         "indices": jnp.where(sel_valid, top_idx, -1)[0],
                         "gates": gates, **stats}
        return out
