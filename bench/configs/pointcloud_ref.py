"""Plain ``jax.numpy`` reference of the point-cloud regressor and its training
step, written from the paper (arXiv:2506.12541, Eq. 5 and 9-14) and the
configuration file alone.  It imports nothing of the program.

Model: ``n_layers`` x [RMSNorm -> attention -> residual -> RMSNorm ->
SwiGLU -> residual] between a linear embedding of the 7 input features and a
linear head, MSE loss over real points.  Attention is ``full`` (softmax over
every real point) or ``bsa``:

* ball: softmax over the real points of the query's ball (contiguous chunks
  of ``ball_size`` in ball order);
* compression: softmax over the mean-pooled blocks of ``cmp_block`` points
  (a learned position offset ``phi_*.pos`` is added before pooling);
* selection: every group of ``group_size`` queries scores the pooled blocks
  with pooled queries, drops blocks of its own ball, and attends the points
  of its ``top_k`` best blocks;
* the three are summed with per-head sigmoid gates; padded rows are zero.

The weights are drawn from the seed in the same order of random draws as the
program's initialiser, so both start from the same point without the
reference taking anything the program made.  Computation is per sample, one
layer at a time under ``jax.checkpoint``, so the reference fits beside a
large batch.  ``precision`` states what it computes in: the dtype the
activations are kept in, and the dtype each class of matmul rounds its
operands to before an exact product with float32 accumulation -- ``dense``
(the embedding, projections, MLP and head), ``score`` (selection scores) and
``attention`` (softmax(q k^T) v of every branch); None keeps float32.  A
configuration file states its precision in that form; the all-bfloat16 one
is the control that must come out as not correct.

Selection is a discrete top-k.  ``select`` replays another run's block ids
(``-1``: keep this run's own); each replayed row is then held to being a
top-k of this run's scores by ``gap`` = (own k-th score - lowest replayed
score) / (1 + |own k-th score|), <= 0 for an exact top-k.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
BRANCHES = ("ball", "cmp", "slc")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _dense_init(key, d_in, d_out, *, scale=None, bias=False):
    scale = math.sqrt(2.0 / d_in) if scale is None else scale
    p = {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def _layer_init(key, m, b):
    d, h, hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    k_attn, k_ffn = jax.random.split(key)
    kq, kk, kv, ko, kb = jax.random.split(k_attn, 5)
    attn = {"wq": _dense_init(kq, d, h * hd), "wk": _dense_init(kk, d, hkv * hd),
            "wv": _dense_init(kv, d, hkv * hd), "wo": _dense_init(ko, h * hd, d)}
    if m["attention"] == "bsa":
        pk, pv, pq, _ = jax.random.split(kb, 4)
        pos = lambda k: {"pos": jax.random.normal(
            k, (b["cmp_block"], hd), jnp.float32) * 0.02}
        attn["bsa"] = {"phi_k": pos(pk), "phi_v": pos(pv), "phi_q": pos(pq),
                       "gates": {n: jnp.zeros((h,), jnp.float32)
                                 for n in BRANCHES}}
    kg, ku, kd = jax.random.split(k_ffn, 3)
    ffn = {"gate": _dense_init(kg, d, m["d_ff"]), "up": _dense_init(ku, d, m["d_ff"]),
           "down": _dense_init(kd, m["d_ff"], d)}
    ones = {"g": jnp.ones((d,), jnp.float32)}
    return {"norm1": ones, "attn": attn, "norm2": dict(ones), "ffn": ffn}


def check_config(cfg: dict) -> None:
    """Raise on a configuration this reference does not implement."""
    m, b = cfg["model"], cfg.get("bsa", {})
    if m["attention"] not in ("bsa", "full"):
        raise ValueError(f"attention {m['attention']!r} has no reference")
    if m["attention"] == "bsa":
        want = {"phi": "mean", "gate_mode": "scalar", "query_cmp_selection": True,
                "group_compression": False, "mask_own_ball": True}
        bad = {k: b.get(k) for k, v in want.items() if b.get(k) != v}
        if bad or b["group_size"] % b["cmp_block"]:
            raise ValueError(f"BSA variant {bad or b} has no reference")


def init(seed: int, cfg: dict) -> dict:
    """Weights for ``seed``: float32, laid out as the program lays them out."""
    check_config(cfg)
    m, b = cfg["model"], cfg.get("bsa", {})
    ke, kl, kh = jax.random.split(jax.random.PRNGKey(seed), 3)
    layers = jax.vmap(lambda k: _layer_init(k, m, b))(
        jax.random.split(kl, m["n_layers"]))
    return {"embed": _dense_init(ke, m["in_dim"], m["d_model"], bias=True),
            "layers": layers,
            "final_norm": {"g": jnp.ones((m["d_model"],), jnp.float32)},
            "head": _dense_init(kh, m["d_model"], m["out_dim"], scale=0.02,
                                bias=True)}


# ---------------------------------------------------------------------------
# forward, one sample
# ---------------------------------------------------------------------------

FLOAT32 = {"activations": "float32",
           "operands": {"dense": None, "score": None, "attention": None}}
BFLOAT16 = {"activations": "bfloat16",
            "operands": {"dense": "bfloat16", "score": "bfloat16",
                         "attention": "bfloat16"}}


def resolve(precision: dict | None):
    """(activation dtype, {matmul class: operand dtype or None})."""
    p = precision or FLOAT32
    ops = {k: None if v is None else jnp.dtype(v) for k, v in p["operands"].items()}
    if set(ops) != set(FLOAT32["operands"]):
        raise ValueError(f"matmul classes {sorted(ops)} are not "
                         f"{sorted(FLOAT32['operands'])}")
    return jnp.dtype(p["activations"]), ops


def round_bf16(x):
    """float32 -> the nearest bfloat16 value (ties to even), kept in float32.
    Integer arithmetic on the bits: a compiler may drop a float32 -> bfloat16
    -> float32 round trip as excess precision, and does on the TPU."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


@jax.custom_vjp
def _operand(x):
    """A matmul operand rounded to bfloat16; its gradient passes through."""
    return round_bf16(x)


_operand.defvjp(lambda x: (round_bf16(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _product(y):
    """A matmul's float32 result; the gradient flowing back into the matmul
    is rounded to bfloat16, as the matmuls of the backward pass round it."""
    return y


_product.defvjp(lambda y: (y, None), lambda _, g: (round_bf16(g),))


def _mm(spec, a, b, rnd):
    """einsum with float32 accumulation.  ``rnd`` (bfloat16 or None): the
    operands of float32 inputs are rounded to bfloat16 first, in the forward
    and the backward pass, as one bfloat16 pass of a matrix unit does; the
    products of bfloat16 values are exact at the reference's ``"highest"``
    precision, so only the float32 accumulation remains."""
    if rnd is None or a.dtype != jnp.float32:
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    if rnd != jnp.bfloat16:
        raise ValueError(f"matmul operands in {rnd} have no rounding here")
    return _product(jnp.einsum(spec, _operand(a), _operand(b.astype(a.dtype)),
                               preferred_element_type=jnp.float32))


def _dense(p, x, ops):
    y = _mm("...i,io->...o", x, p["w"].astype(x.dtype), ops["dense"])
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(x.dtype)


def _rmsnorm(p, x, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * p["g"].astype(x.dtype)


def _softmax_attend(q, k, v, valid, ops):
    """q (..., M, D), k/v (..., L, D), valid broadcastable to (..., M, L).
    fp32 logits; a row with no valid key gives zeros."""
    logits = _mm("...md,...ld->...ml", q, k, ops["attention"])
    logits = logits / math.sqrt(q.shape[-1])
    logits = jnp.where(valid, logits, NEG_INF)
    mx = jnp.maximum(logits.max(-1, keepdims=True), NEG_INF / 2)
    p = jnp.where(valid, jnp.exp(logits - mx), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)
    out = _mm("...ml,...ld->...md", p.astype(v.dtype), v, ops["attention"])
    return out.astype(v.dtype)


def _pool(t, pos, mask, ell):
    """Masked mean of (t + pos) over blocks of ``ell`` rows: (N, H, D) ->
    (N / ell, H, D)."""
    n, h, d = t.shape
    tb = t.reshape(n // ell, ell, h, d) + pos.astype(t.dtype)[None, :, None, :]
    mb = mask.reshape(n // ell, ell)
    tb = jnp.where(mb[:, :, None, None], tb, jnp.zeros((), t.dtype))
    cnt = jnp.maximum(mb.sum(-1), 1).astype(jnp.float32)
    return (tb.sum(1) / cnt[:, None, None]).astype(t.dtype)


def _heads_first(t):
    return t.transpose(1, 0, 2)                                   # (H, N, D)


def _bsa(p, q, k, v, mask, b, select, ops):
    """q (N, H, D), k/v (N, Hkv, D), mask (N,) -> (out (N, H, D), replay
    gap, selected ids (G, Hkv, k*), -1 where invalid)."""
    n, h, d = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    m, ell, g = b["ball_size"], b["cmp_block"], b["group_size"]
    nb = n // ell
    kr = jnp.repeat(k, rep, axis=1)
    vr = jnp.repeat(v, rep, axis=1)

    # ball: softmax over the real points of the query's own ball
    qb, kb, vb = (t.reshape(n // m, m, h, d).transpose(0, 2, 1, 3)
                  for t in (q, kr, vr))
    ball_valid = mask.reshape(n // m, 1, 1, m)
    out_ball = _softmax_attend(qb, kb, vb, ball_valid, ops
                               ).transpose(0, 2, 1, 3).reshape(n, h, d)

    # compression: softmax over the pooled blocks that hold a real point
    k_cmp = _pool(k, p["phi_k"]["pos"], mask, ell)                # (nb, Hkv, D)
    v_cmp = _pool(v, p["phi_v"]["pos"], mask, ell)
    blk_valid = mask.reshape(nb, ell).any(-1)
    out_cmp = _softmax_attend(
        _heads_first(q), _heads_first(jnp.repeat(k_cmp, rep, axis=1)),
        _heads_first(jnp.repeat(v_cmp, rep, axis=1)), blk_valid[None, None, :],
        ops).transpose(1, 0, 2)

    # selection: group scores from pooled queries, own ball excluded
    q_cmp = _pool(q, p["phi_q"]["pos"], mask, ell)                # (nb, H, D)
    s = _mm("mkrd,nkd->mkn", q_cmp.reshape(nb, hkv, rep, d), k_cmp,
            ops["score"])                                         # (nb, Hkv, nb)
    rows = g // ell
    n_groups = nb // rows
    s = s.reshape(n_groups, rows, hkv, nb).mean(1) / math.sqrt(d)
    grp_ball = jnp.arange(n_groups) * g // m
    blk_ball = jnp.arange(nb) * ell // m
    s = jnp.where(blk_valid[None, None, :]
                  & (grp_ball[:, None] != blk_ball[None, :])[:, None, :],
                  s, NEG_INF)
    k_star = min(b["top_k"], nb)
    top_vals, top_idx = jax.lax.top_k(s, k_star)                  # (G, Hkv, k*)
    if select is None:
        idx, vals, gap = top_idx, top_vals, jnp.float32(-jnp.inf)
    else:
        replay = select[..., :1] >= 0
        idx = jnp.where(replay, jnp.maximum(select, 0), top_idx)
        vals = jnp.where(replay & (select < 0), NEG_INF,
                         jnp.take_along_axis(s, idx, axis=-1))
        kth = top_vals[..., -1]
        live = replay[..., 0] & (kth > NEG_INF / 2)
        gap = jnp.max(jnp.where(live, (kth - vals.min(-1)) / (1 + jnp.abs(kth)),
                                -jnp.inf))
    group_live = mask.reshape(n_groups, g).any(-1)
    sel_valid = (vals > NEG_INF / 2) & group_live[:, None, None]  # (G, Hkv, k*)
    kblk = k.reshape(nb, ell, hkv, d).transpose(2, 0, 1, 3)       # (Hkv, nb, ell, D)
    vblk = v.reshape(nb, ell, hkv, d).transpose(2, 0, 1, 3)
    tok = mask.reshape(nb, ell)
    ids = idx.transpose(1, 0, 2)                                  # (Hkv, G, k*)
    gather = lambda t: jax.vmap(lambda th, ih: th[ih])(t, ids)    # (Hkv, G, k*, ell, ..)
    ks, vs = gather(kblk), gather(vblk)
    tv = tok[ids] & sel_valid.transpose(1, 0, 2)[..., None]       # (Hkv, G, k*, ell)
    L = k_star * ell
    ks = ks.reshape(hkv, n_groups, 1, L, d)
    vs = vs.reshape(hkv, n_groups, 1, L, d)
    qs = q.reshape(n_groups, g, hkv, rep, d).transpose(2, 0, 3, 1, 4)  # (Hkv,G,rep,g,D)
    out_slc = _softmax_attend(qs, ks, vs, tv.reshape(hkv, n_groups, 1, 1, L), ops)
    out_slc = out_slc.transpose(1, 3, 0, 2, 4).reshape(n, h, d)

    out = sum(jax.nn.sigmoid(p["gates"][name].astype(jnp.float32))[None, :, None]
              * o.astype(jnp.float32)
              for name, o in zip(BRANCHES, (out_ball, out_cmp, out_slc)))
    out = jnp.where(mask[:, None, None], out, 0.0).astype(q.dtype)
    return out, gap, jnp.where(vals > NEG_INF / 2, idx, -1)


def _attention(p, x, mask, cfg, select, ops):
    m = cfg["model"]
    n = x.shape[0]
    h, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _dense(p["wq"], x, ops).reshape(n, h, hd)
    k = _dense(p["wk"], x, ops).reshape(n, hkv, hd)
    v = _dense(p["wv"], x, ops).reshape(n, hkv, hd)
    if m["attention"] == "bsa":
        out, gap, ids = _bsa(p["bsa"], q, k, v, mask, cfg["bsa"], select, ops)
    else:
        rep = h // hkv
        out = _softmax_attend(_heads_first(q),
                              _heads_first(jnp.repeat(k, rep, axis=1)),
                              _heads_first(jnp.repeat(v, rep, axis=1)),
                              mask[None, None, :], ops).transpose(1, 0, 2)
        gap, ids = jnp.float32(-jnp.inf), jnp.zeros((), jnp.int32)
    return _dense(p["wo"], out.reshape(n, h * hd), ops), gap, ids


def forward(params, feats, mask, cfg, select=None, precision=None):
    """One ball-ordered sample: feats (N, in_dim), mask (N,) ->
    (predictions (N, out_dim) float32, selected ids (n_layers, G, Hkv, k*)
    (BSA; a placeholder otherwise), largest replay gap)."""
    eps = cfg["model"]["norm_eps"]
    act, ops = resolve(precision)
    x = _dense(params["embed"], feats.astype(act), ops)

    def layer(x, xs):
        lp, sel = xs
        a, gap, ids = _attention(lp["attn"], _rmsnorm(lp["norm1"], x, eps),
                                 mask, cfg, sel, ops)
        x = x + a
        hh = _rmsnorm(lp["norm2"], x, eps)
        f = lp["ffn"]
        u = jax.nn.silu(_dense(f["gate"], hh, ops))
        return x + _dense(f["down"], u * _dense(f["up"], hh, ops), ops), (gap, ids)

    x, (gaps, ids) = jax.lax.scan(jax.checkpoint(layer), x,
                                  (params["layers"], select))
    x = _rmsnorm(params["final_norm"], x, eps)
    return _dense(params["head"], x, ops).astype(jnp.float32), ids, gaps.max()


def sample_loss(params, feats, target, mask, denom, cfg, select=None,
                precision=None):
    """This sample's share of the batch's MSE (``denom``: real points of
    the whole batch x out_dim), and its largest replay gap."""
    pred, ids, gap = forward(params, feats, mask, cfg, select, precision)
    err = jnp.where(mask[:, None], (pred - target) ** 2, 0.0)
    return err.sum() / denom, (gap, ids)


# ---------------------------------------------------------------------------
# training: clipped AdamW with linear warm-up into a cosine decay
# ---------------------------------------------------------------------------

def learning_rate(step, t):
    """Learning rate at optimizer step ``step`` (0 for the first)."""
    step = float(step)
    if step < t["warmup_steps"]:
        return t["base_lr"] * step / max(t["warmup_steps"], 1)
    prog = min(max((step - t["warmup_steps"])
                   / max(t["total_steps"] - t["warmup_steps"], 1), 0.0), 1.0)
    return t["base_lr"] * 0.5 * (1.0 + math.cos(math.pi * prog))


def clip(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(params, grads, state, lr, t, b1=0.9, b2=0.999, eps=1e-8):
    """One AdamW step (decoupled weight decay) in float32."""
    step = state["step"] + 1
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + t["weight_decay"] * p), params, m, v)
    return new, {"m": m, "v": v, "step": step}


def train_steps(params, batches, cfg, trainer, *, precision=None,
                selects=None, drop_half=False):
    """Run len(batches) training steps from ``params`` (float32), sample by
    sample, computing in ``precision`` (see ``forward``).
    ``selects[s]`` (n_layers, B, G, Hkv, k*) replays step s's block ids.
    ``drop_half`` is a planted fault: each step learns from the first half
    of its batch only.

    Returns per step: loss, clipped gradient (the optimizer's input),
    parameters after the step, selected ids (n_layers, B, ...) and the
    largest replay gap."""
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, f, t, m, d, s: sample_loss(p, f, t, m, d, cfg, s, precision),
        has_aux=True))
    state = {"m": jax.tree.map(jnp.zeros_like, params),
             "v": jax.tree.map(jnp.zeros_like, params), "step": 0}
    out = []
    for s, batch in enumerate(batches):
        rows = len(batch["mask"]) // 2 if drop_half else len(batch["mask"])
        mask = np.asarray(batch["mask"])[:rows]
        denom = float(mask.sum() * cfg["model"]["out_dim"])
        loss, grads, gap, ids = 0.0, None, -np.inf, []
        for r in range(rows):
            sel = None if selects is None else selects[s][:, r]
            (l_r, (g_r, i_r)), gr = grad_fn(
                params, batch["feats"][r], batch["target"][r], batch["mask"][r],
                denom, sel)
            loss = loss + l_r
            gap = max(gap, float(g_r))
            ids.append(i_r)
            grads = gr if grads is None else jax.tree.map(jnp.add, grads, gr)
        grads = clip(grads, trainer["max_grad_norm"])
        params, state = adamw(params, grads,
                              state, learning_rate(s, trainer), trainer)
        out.append({"loss": float(loss), "grads": grads, "params": params,
                    "ids": jnp.stack(ids, axis=1), "gap": gap})
    return out
