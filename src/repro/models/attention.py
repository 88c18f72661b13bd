"""GQA attention: flash-style chunked prefill/train path + decode path.

The train/prefill path is a pure-jnp flash-attention (two-level chunked
online softmax).  This keeps activation memory O(S · chunk) instead of
O(S²) — essential for the 32k prefill dry-runs — and doubles as a second
oracle for the Pallas kernel in ``repro.kernels.flash_attention``.

Supports: GQA (num_kv_heads < num_heads), causal masking, sliding-window
attention (Mixtral-style), encoder (bidirectional) mode, qk-norm (Qwen3),
QKV bias (Qwen2), no position embedding and a given score scale
(Granite-4.0-H).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, rms_norm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_attention(rng, cfg: ModelConfig, dtype=jnp.float32):
    d, h = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(rng, 4)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, nq * h)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, nkv * h)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, nkv * h)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (nq * h, d)) * ((nq * h) ** -0.5)
               ).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * h,), dtype)
        p["bk"] = jnp.zeros((nkv * h,), dtype)
        p["bv"] = jnp.zeros((nkv * h,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((h,), dtype)
        p["k_norm"] = jnp.ones((h,), dtype)
    return p


def _project_qkv(params, cfg: ModelConfig, x: jnp.ndarray,
                 positions: jnp.ndarray):
    """x: [B, S, d] -> q [B,S,nq,h], k/v [B,S,nkv,h] (normed, and roped
    unless ``cfg.rope`` is off)."""
    B, S, _ = x.shape
    h = cfg.head_dim
    q = jnp.einsum("bsd,dk->bsk", x, params["wq"])
    k = jnp.einsum("bsd,dk->bsk", x, params["wk"])
    v = jnp.einsum("bsd,dk->bsk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, cfg.num_heads, h)
    k = k.reshape(B, S, cfg.num_kv_heads, h)
    v = v.reshape(B, S, cfg.num_kv_heads, h)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.rms_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_eps)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# Flash-style chunked attention (train / prefill)
# ---------------------------------------------------------------------------


def flash_attention_jnp(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True,
                        window: Optional[int] = None,
                        chunk_q: int = 512, chunk_k: int = 512,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Online-softmax attention.

    q: [B, S, Hq, D]; k, v: [B, S, Hkv, D] with Hq % Hkv == 0.
    Returns [B, S, Hq, D].  S must be divisible by the chunk sizes (the
    callers pad); masking is by absolute position (causal and/or sliding
    window of size ``window``: query i attends to keys in
    (i - window, i]).  Scores are scaled by ``scale``, by default
    ``D ** -0.5``.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    nq, nk = S // chunk_q, S // chunk_k

    # [B, Hkv, group, nq, cq, D]
    qg = q.reshape(B, nq, chunk_q, Hkv, group, D).transpose(0, 3, 4, 1, 2, 5)
    kg = k.reshape(B, nk, chunk_k, Hkv, D).transpose(0, 3, 1, 2, 4)
    vg = v.reshape(B, nk, chunk_k, Hkv, D).transpose(0, 3, 1, 2, 4)

    q_pos = jnp.arange(S).reshape(nq, chunk_q)
    k_pos = jnp.arange(S).reshape(nk, chunk_k)

    kg_t = kg.transpose(2, 0, 1, 3, 4)  # [nk, B, Hkv, ck, D]
    vg_t = vg.transpose(2, 0, 1, 3, 4)

    def per_qchunk(args):
        qp, qc = args  # qp: [cq] absolute positions; qc: [B,Hkv,group,cq,D]
        m0 = jnp.full(qc.shape[:-1], NEG_INF, jnp.float32)
        l0 = jnp.zeros(qc.shape[:-1], jnp.float32)
        a0 = jnp.zeros(qc.shape, jnp.float32)

        def body(carry, inp):
            m, l, acc = carry
            kc, vc, kp = inp  # [B,Hkv,ck,D], [B,Hkv,ck,D], [ck]
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qc.astype(jnp.float32),
                           kc.astype(jnp.float32)) * scale
            mask = jnp.ones((chunk_q, chunk_k), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window is not None:
                mask &= qp[:, None] - kp[None, :] < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kg_t, vg_t, k_pos))
        return acc / jnp.maximum(l[..., None], 1e-30)

    qg_t = qg.transpose(3, 0, 1, 2, 4, 5)  # [nq, B, Hkv, group, cq, D]
    out = jax.lax.map(per_qchunk, (q_pos, qg_t))  # [nq, B, Hkv, group, cq, D]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, Hq, D)
    return out.astype(q.dtype)


def _pick_chunk(S: int, preferred: int = 512) -> int:
    c = min(preferred, S)
    while S % c:
        c //= 2
    return max(c, 1)


def attention_forward(params, cfg: ModelConfig, x: jnp.ndarray,
                      positions: jnp.ndarray) -> jnp.ndarray:
    """Full-sequence (train / prefill) attention over x: [B, S, d]."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    c = _pick_chunk(S)
    out = flash_attention_jnp(q, k, v, causal=cfg.causal,
                              window=cfg.sliding_window,
                              chunk_q=c, chunk_k=c,
                              scale=cfg.attention_scale)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return jnp.einsum("bsk,kd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# Decode path (single new token against a KV cache)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    h = cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, h), dtype),
        "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, h), dtype),
    }


def attention_decode(params, cfg: ModelConfig, x: jnp.ndarray,
                     cache: dict, index: jnp.ndarray):
    """x: [B, 1, d]; index: scalar position of the new token.

    Returns (out [B, 1, d], updated cache).  The sliding-window variant
    only attends to the last ``window`` cache slots by masking (the cache
    retains max_len slots; ring-buffer compaction is a serving-layer
    optimization, see pipeline/).
    """
    B = x.shape[0]
    positions = jnp.full((B, 1), index, jnp.int32)
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)

    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, index, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, index, 0, 0))
    S = k.shape[1]
    Hq, Hkv = cfg.num_heads, cfg.num_kv_heads
    group = Hq // Hkv
    qg = q.reshape(B, 1, Hkv, group, cfg.head_dim)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (
        cfg.attention_scale or cfg.head_dim ** -0.5)
    kp = jnp.arange(S)
    mask = kp <= index
    if cfg.sliding_window is not None:
        mask &= kp > index - cfg.sliding_window
    s = jnp.where(mask[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    out = out.reshape(B, 1, Hq * cfg.head_dim).astype(x.dtype)
    out = jnp.einsum("bsk,kd->bsd", out, params["wo"])
    return out, {"k": k, "v": v}
