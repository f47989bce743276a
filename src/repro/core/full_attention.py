"""Full (dense) attention baseline — the paper's accuracy upper bound."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.backend import resolve_backend

__all__ = ["full_attention"]


def full_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   mask: jnp.ndarray | None = None, causal: bool = False,
                   backend=None) -> jnp.ndarray:
    """q: (B,N,Hq,D); k,v: (B,L,Hkv,D); mask: (B,L) key validity.

    GQA-native: K/V are passed to the backend un-repeated (kernels share one
    K/V fetch per GQA group; the jnp reference repeats internally).
    ``backend`` names an attention backend (or passes a Backend object);
    None resolves via the usual precedence chain (default "auto").
    """
    bk = resolve_backend(backend)
    with jax.named_scope("attention"):
        return bk.flash(q, k, v, key_valid=mask, causal=causal)
