"""The paper's model: 18 × [RMSNorm → BSA → RMSNorm → SwiGLU] on ball-ordered
point clouds, MSE regression head (airflow pressure / stress field).

The attention backend is switchable (``bsa`` | ``full`` | ``erwin``) to
reproduce Tables 1–3.  Inputs arrive ball-ordered (data pipeline applies the
ball-tree permutation) with a validity mask for padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.distributed import constrain
from repro.layers.nn import dense, dense_init, rmsnorm, rmsnorm_init, swiglu, swiglu_init
from repro.models.attention_layer import attention_layer_apply, attention_layer_init


def pc_init(key, mcfg) -> dict:
    pd = mcfg.pdtype()
    ke, kl, kh = jax.random.split(key, 3)
    layers = jax.vmap(lambda k: _layer_init(k, mcfg, pd))(
        jax.random.split(kl, mcfg.n_layers))
    return {
        "embed": dense_init(ke, mcfg.in_dim, mcfg.d_model, param_dtype=pd, bias=True),
        "layers": layers,
        "final_norm": rmsnorm_init(mcfg.d_model, param_dtype=pd),
        "head": dense_init(kh, mcfg.d_model, mcfg.out_dim, param_dtype=pd,
                           scale=0.02, bias=True),
    }


def _layer_init(key, mcfg, pd):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": rmsnorm_init(mcfg.d_model, param_dtype=pd),
        "attn": attention_layer_init(k1, mcfg, param_dtype=pd),
        "norm2": rmsnorm_init(mcfg.d_model, param_dtype=pd),
        "ffn": swiglu_init(k2, mcfg.d_model, mcfg.d_ff, param_dtype=pd),
    }


def pc_apply(params, feats, *, mcfg, mask=None, erwin_level_of=None,
             offsets=None, select=None, return_selection: bool = False):
    """feats: (B, N, in_dim) ball-ordered; mask: (B, N).  → (B, N, out_dim).

    ``offsets`` (S+1,) int32 selects the packed-varlen layout (docs/varlen.md):
    feats is then ONE packed row (B=1) of concatenated samples and every
    attention layer runs segment-isolated with no dummy batch slots.

    ``return_selection`` (BSA only) also returns every layer's selected
    block ids, ``{"indices": (n_layers, B, G, Hkv, k*)}``; ``select`` (that
    shape) replays ids instead — e.g. another layout's or backend's, so two
    runs can be compared without a near-tie in top-k breaking differently —
    and adds per-layer ``gap``/``flips`` (``core.bsa._select_blocks``)."""
    cdt = mcfg.cdtype()
    with jax.named_scope("embed"):
        x = dense(params["embed"], feats.astype(cdt))
    x = constrain(x, "batch", "seq_res", "d_model")
    want_sel = return_selection or select is not None

    def layer(lp, x, level, sel=None):
        with jax.named_scope("norm"):
            h = rmsnorm(lp["norm1"], x, mcfg.norm_eps)
        h = attention_layer_apply(lp["attn"], h, mcfg=mcfg, causal=False,
                                  mask=mask, positions=None, rope=False,
                                  erwin_level=level, offsets=offsets,
                                  select=sel, return_selection=want_sel)
        h, sel = h if want_sel else (h, None)
        x = x + h
        with jax.named_scope("norm"):
            h = rmsnorm(lp["norm2"], x, mcfg.norm_eps)
        with jax.named_scope("ffn"):
            x = x + swiglu(lp["ffn"], h)
        return constrain(x, "batch", "seq_res", "d_model"), sel

    if mcfg.attention == "erwin" and erwin_level_of is None:
        # Erwin's coarsen/refine cycle: levels 0,1,2,1,0,...
        cyc = [0, 1, 2, 1]
        erwin_level_of = lambda i: cyc[i % len(cyc)]

    sels = None
    if erwin_level_of is not None:
        # per-layer levels differ → unrolled loop (baseline only, 18 layers)
        for i in range(mcfg.n_layers):
            lp = jax.tree.map(lambda t: t[i], params["layers"])
            x, _ = layer(lp, x, erwin_level_of(i))
    else:
        fn = functools.partial(layer, level=0)
        if mcfg.remat:
            fn = jax.checkpoint(fn)
        def body(x, xs):
            lp, sel = xs
            return fn(lp, x, sel=sel)
        x, sels = jax.lax.scan(body, x, (params["layers"], select))

    with jax.named_scope("norm"):
        x = rmsnorm(params["final_norm"], x, mcfg.norm_eps)
    with jax.named_scope("head"):
        out = dense(params["head"], x).astype(jnp.float32)
    return (out, sels) if want_sel else out


def pc_loss(params, batch, *, mcfg):
    """batch: {feats (B,N,F), target (B,N,out_dim), mask (B,N)} → MSE.
    An optional ``offsets`` key selects the packed-varlen layout."""
    pred = pc_apply(params, batch["feats"], mcfg=mcfg, mask=batch.get("mask"),
                    offsets=batch.get("offsets"))
    with jax.named_scope("loss"):
        err = (pred - batch["target"].astype(jnp.float32)) ** 2
        m = batch.get("mask")
        if m is not None:
            err = jnp.where(m[..., None], err, 0.0)
            denom = jnp.maximum(m.sum() * mcfg.out_dim, 1)
        else:
            denom = err.size
        loss = err.sum() / denom
    return loss, {"mse": loss}
