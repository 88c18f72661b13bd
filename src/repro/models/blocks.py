"""Block (pipeline-unit) definitions.

A *block* is the homogeneous super-layer the pipeline scheduler moves
between stages (DESIGN.md §4): dense/moe/vlm/audio → one attention
sublayer; ssm → one Mamba2 sublayer; hybrid (Jamba) → the period-8
super-block (1 attn + 7 mamba), MoE on alternating sublayers.

Every sublayer is pre-norm:  x += Mixer(LN(x));  x += FFN(LN(x)).
Blocks expose three modes:

* ``block_forward``   — full sequence (train / encoder / prefill compute)
* ``block_prefill``   — full sequence + returns the decode cache
* ``block_decode``    — one token + cache -> one token + cache

Parameters of all blocks of a model are *stacked* along a leading
``num_blocks`` axis so the assignment of blocks to pipeline stages can be
a runtime argument (recompile-free rebalancing, DESIGN.md §2).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba2 as mamba_lib
from repro.models import moe as moe_lib
from repro.models.sharding_ctx import constrain
from repro.models.layers import init_mlp, init_rms_norm, mlp, rms_norm

ZERO_STATS = dict(aux_loss=0.0, router_z=0.0, dropped_frac=0.0)


def _sublayer_kinds(cfg: ModelConfig):
    """[(mixer_kind, ffn_kind)] per sublayer of one block."""
    out = []
    for i, mixer in enumerate(cfg.layer_pattern):
        if cfg.family == "ssm":
            ffn = "none"
        elif cfg.moe is not None and cfg.sublayer_is_moe(i):
            ffn = "moe"
        elif cfg.d_ff > 0:
            ffn = "dense"
        else:
            ffn = "none"
        out.append((mixer, ffn))
    return out


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_block(rng, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    params = {}
    kinds = _sublayer_kinds(cfg)
    rngs = jax.random.split(rng, 2 * len(kinds))
    for i, (mixer, ffn) in enumerate(kinds):
        sub = {"ln1": init_rms_norm(cfg.d_model, dtype)}
        if mixer == "attn":
            sub["mixer"] = attn_lib.init_attention(rngs[2 * i], cfg, dtype)
        else:
            sub["mixer"] = mamba_lib.init_mamba(rngs[2 * i], cfg, dtype)
        if ffn != "none":
            sub["ln2"] = init_rms_norm(cfg.d_model, dtype)
            if ffn == "moe":
                sub["ffn"] = moe_lib.init_moe(rngs[2 * i + 1], cfg.d_model,
                                              cfg.moe, dtype)
            else:
                sub["ffn"] = init_mlp(rngs[2 * i + 1], cfg.d_model, cfg.d_ff,
                                      dtype)
        params[f"sub{i}"] = sub
    return params


def init_stacked_blocks(rng, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    rngs = jax.random.split(rng, cfg.num_blocks)
    return jax.vmap(lambda r: init_block(r, cfg, dtype))(rngs)


# ---------------------------------------------------------------------------
# Forward modes
# ---------------------------------------------------------------------------


def _apply_ffn(sub, cfg: ModelConfig, ffn_kind: str, x):
    """Returns (delta, stats)."""
    if ffn_kind == "none":
        return None, ZERO_STATS
    h = rms_norm(x, sub["ln2"]["scale"], cfg.rms_eps)
    if ffn_kind == "moe":
        y, st = moe_lib.moe_forward(sub["ffn"], cfg.moe, h)
        return y, dict(aux_loss=st.aux_loss, router_z=st.router_z,
                       dropped_frac=st.dropped_frac)
    return mlp(sub["ffn"], h), ZERO_STATS


def block_forward(params, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence block application; returns (x, summed router stats)."""
    stats = dict(ZERO_STATS)
    x = constrain(x)
    for i, (mixer, ffn) in enumerate(_sublayer_kinds(cfg)):
        sub = params[f"sub{i}"]
        h = rms_norm(x, sub["ln1"]["scale"], cfg.rms_eps)
        # Scope names label the mixer's and FFN's operations in the HLO
        # metadata, for per-scope device time.
        if mixer == "attn":
            with jax.named_scope("attention"):
                x = x + attn_lib.attention_forward(sub["mixer"], cfg, h,
                                                   positions)
        else:
            with jax.named_scope("ssd"):
                x = x + mamba_lib.mamba_forward(sub["mixer"], cfg, h)
        with jax.named_scope("moe" if ffn == "moe" else "mlp"):
            delta, st = _apply_ffn(sub, cfg, ffn, x)
        if delta is not None:
            x = x + delta
        stats = {k: stats[k] + st[k] for k in stats}
    return x, stats


# -- caches -------------------------------------------------------------------


def init_block_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict:
    cache = {}
    for i, (mixer, _) in enumerate(_sublayer_kinds(cfg)):
        if mixer == "attn":
            cache[f"sub{i}"] = attn_lib.init_kv_cache(cfg, batch, max_len, dtype)
        else:
            cache[f"sub{i}"] = mamba_lib.init_mamba_cache(cfg, batch, dtype)
    return cache


def init_stacked_cache(cfg: ModelConfig, batch: int, max_len: int,
                       dtype) -> Dict:
    one = init_block_cache(cfg, batch, max_len, dtype)
    return jax.tree.map(
        lambda a: jnp.zeros((cfg.num_blocks,) + a.shape, a.dtype), one)


def block_prefill(params, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray, cache: Dict
                  ) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence forward that also fills this block's decode cache."""
    S = x.shape[1]
    new_cache = {}
    for i, (mixer, ffn) in enumerate(_sublayer_kinds(cfg)):
        sub = params[f"sub{i}"]
        h = rms_norm(x, sub["ln1"]["scale"], cfg.rms_eps)
        if mixer == "attn":
            q, k, v = attn_lib._project_qkv(sub["mixer"], cfg, h, positions)
            c = attn_lib._pick_chunk(S)
            o = attn_lib.flash_attention_jnp(
                q, k, v, causal=cfg.causal, window=cfg.sliding_window,
                chunk_q=c, chunk_k=c)
            o = o.reshape(x.shape[0], S, cfg.num_heads * cfg.head_dim)
            x = x + jnp.einsum("bsk,kd->bsd", o, sub["mixer"]["wo"])
            kc = cache[f"sub{i}"]
            new_cache[f"sub{i}"] = {
                "k": jax.lax.dynamic_update_slice(
                    kc["k"], k.astype(kc["k"].dtype), (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    kc["v"], v.astype(kc["v"].dtype), (0, 0, 0, 0)),
            }
        else:
            o, mc = mamba_prefill(sub["mixer"], cfg, h)
            x = x + o
            kc = cache[f"sub{i}"]
            new_cache[f"sub{i}"] = {
                "conv": mc["conv"].astype(kc["conv"].dtype),
                "ssm": mc["ssm"].astype(kc["ssm"].dtype),
            }
        delta, _ = _apply_ffn(sub, cfg, ffn, x)
        if delta is not None:
            x = x + delta
    return x, new_cache


def block_decode(params, cfg: ModelConfig, x: jnp.ndarray,
                 cache: Dict, index: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode through one block."""
    new_cache = {}
    for i, (mixer, ffn) in enumerate(_sublayer_kinds(cfg)):
        sub = params[f"sub{i}"]
        h = rms_norm(x, sub["ln1"]["scale"], cfg.rms_eps)
        if mixer == "attn":
            o, new_cache[f"sub{i}"] = attn_lib.attention_decode(
                sub["mixer"], cfg, h, cache[f"sub{i}"], index)
        else:
            o, new_cache[f"sub{i}"] = mamba_lib.mamba_decode(
                sub["mixer"], cfg, h, cache[f"sub{i}"])
        x = x + o
        delta, _ = _apply_ffn(sub, cfg, ffn, x)
        if delta is not None:
            x = x + delta
    return x, new_cache


# ---------------------------------------------------------------------------
# Mamba prefill helper (forward + cache extraction)
# ---------------------------------------------------------------------------


def mamba_prefill(params, cfg: ModelConfig, x: jnp.ndarray):
    """Like mamba_forward but also returns the decode cache."""
    s = cfg.ssm
    B_, S, d = x.shape
    din = s.d_inner(d)
    N = s.d_state
    H = s.num_heads(d)
    P = s.head_dim

    z, xBC_pre, dt = mamba_lib._project(params, x)
    xBC = jax.nn.silu(mamba_lib._causal_conv(
        xBC_pre, params["conv_w"], params["conv_b"]))
    xs = xBC[..., :din].reshape(B_, S, H, P)
    Bm = xBC[..., din:din + N]
    Cm = xBC[..., din + N:]
    dtv = jax.nn.softplus(dt.astype(jnp.float32)
                          + params["dt_bias"]).astype(x.dtype)
    A = -jnp.exp(params["A_log"]).astype(x.dtype)
    chunk = min(s.chunk_size, S)
    while S % chunk:
        chunk //= 2
    y, final_state = mamba_lib.ssd_chunked(xs, dtv, A, Bm, Cm, chunk=chunk)
    y = y + xs * params["D"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(B_, S, din)
    y = rms_norm(y * jax.nn.silu(z), params["norm_scale"], cfg.rms_eps)
    out = jnp.einsum("bsk,kd->bsd", y, params["out_proj"])
    # conv cache = last (d_conv - 1) pre-activation conv inputs
    K = s.d_conv
    conv_cache = xBC_pre[:, S - (K - 1):, :] if S >= K - 1 else \
        jnp.pad(xBC_pre, ((0, 0), (K - 1 - S, 0), (0, 0)))
    return out, {"conv": conv_cache, "ssm": final_state}
