"""Shared layer primitives (pure JAX, pytree params)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


def init_rms_norm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                      # [D/2]
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # [...,S,1,D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def init_mlp(rng, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    return {
        "wi": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
        "wg": (jax.random.normal(k2, (d_model, d_ff)) * s_in).astype(dtype),
        "wo": (jax.random.normal(k3, (d_ff, d_model)) * s_out).astype(dtype),
    }


def mlp(params, x: jnp.ndarray) -> jnp.ndarray:
    h = jnp.einsum("...d,df->...f", x, params["wi"])
    g = jnp.einsum("...d,df->...f", x, params["wg"])
    h = jax.nn.silu(g) * h
    return jnp.einsum("...f,fd->...d", h, params["wo"])


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def init_embedding(rng, vocab: int, d_model: int, dtype=jnp.float32):
    return {"table": (jax.random.normal(rng, (vocab, d_model))
                      * (d_model ** -0.5)).astype(dtype)}


def embed(params, tokens: jnp.ndarray,
          multiplier: Optional[float] = None) -> jnp.ndarray:
    x = jnp.take(params["table"], tokens, axis=0)
    return x if multiplier is None else x * multiplier


def init_unembed(rng, d_model: int, vocab: int, dtype=jnp.float32):
    return {"w": (jax.random.normal(rng, (d_model, vocab))
                  * (d_model ** -0.5)).astype(dtype)}


def unembed(params, x: jnp.ndarray,
            scaling: Optional[float] = None) -> jnp.ndarray:
    """Logits of ``x``, divided by ``scaling`` where given."""
    logits = jnp.einsum("...d,dv->...v", x, params["w"])
    return logits if scaling is None else logits / scaling


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy in fp32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
