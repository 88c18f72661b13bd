"""Block (pipeline-unit) definitions.

A *block* is one layer, the unit the pipeline scheduler moves between
stages (DESIGN.md §4): a mixer (attention or Mamba-2) and an FFN (dense
SwiGLU MLP, MoE or none), each pre-norm:

    x += Mixer(LN(x));  x += FFN(LN(x))

(each branch times ``cfg.residual_multiplier`` where the model has one).
Parameters are stacked so that the layer range of a pipeline stage can
be a runtime argument (recompile-free rebalancing, DESIGN.md §2):

* a model whose layers are all of one kind stacks every leaf along a
  leading ``num_layers`` axis under ``sub0`` (``ln1``, ``mixer``,
  ``ln2``, ``ffn``);
* a model that mixes kinds (Jamba, Granite-4.0-H; every layer has an
  FFN) stacks each kind on its own, ``attn/mixer`` ``[n_attn, ...]``,
  ``mamba/mixer``, ``mlp/ffn``, ``moe/ffn``, and the norms
  ``ln1``/``ln2`` per layer ``[num_layers, d]``; the static per-layer
  kinds (:func:`layer_kinds`) give each layer its slot in its kind's
  stack.

:func:`layer_forward` applies layer ``i`` of either layout.  ``i`` may be
traced; a mixed model then picks the kind with ``lax.switch``, so one
compiled program serves every layer range.  ``prefill`` / ``decode``
fill and step the decode caches (KV for attention, conv and SSM state
for Mamba-2), stacked the same way.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba2 as mamba_lib
from repro.models import moe as moe_lib
from repro.models.sharding_ctx import constrain
from repro.models.layers import init_mlp, init_rms_norm, mlp, rms_norm

ZERO_STATS = dict(aux_loss=0.0, router_z=0.0, dropped_frac=0.0)


def _ffn_kind(cfg: ModelConfig, layer: int) -> str:
    if cfg.family == "ssm":
        return "none"
    if cfg.moe is not None and cfg.sublayer_is_moe(layer):
        return "moe"
    return "mlp" if cfg.d_ff > 0 else "none"


def layer_kinds(cfg: ModelConfig) -> List[Tuple[str, str]]:
    """``[(mixer_kind, ffn_kind)]``, one per layer."""
    return [(cfg.layer_kind(i), _ffn_kind(cfg, i))
            for i in range(cfg.num_layers)]


def is_mixed(cfg: ModelConfig) -> bool:
    """Whether the layers differ in kind (the kind-stacked layout)."""
    return len(set(layer_kinds(cfg))) > 1


def _slots(kinds: Sequence[str]) -> List[int]:
    """Each layer's index in the stack of its kind."""
    seen: Dict[str, int] = {}
    out = []
    for k in kinds:
        out.append(seen.get(k, 0))
        seen[k] = out[-1] + 1
    return out


def _at(tree, i):
    return jax.tree.map(lambda p: p[i], tree)


def _residual(cfg: ModelConfig, x, delta):
    if cfg.residual_multiplier is None:
        return x + delta
    return x + delta * cfg.residual_multiplier


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_mixer(kind: str, rng, cfg: ModelConfig, dtype):
    if kind == "attn":
        return attn_lib.init_attention(rng, cfg, dtype)
    return mamba_lib.init_mamba(rng, cfg, dtype)


def _init_ffn(kind: str, rng, cfg: ModelConfig, dtype):
    if kind == "moe":
        return moe_lib.init_moe(rng, cfg.d_model, cfg.moe, dtype)
    return init_mlp(rng, cfg.d_model, cfg.d_ff, dtype)


def init_block(rng, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    """One layer of a model whose layers are all of one kind."""
    mixer, ffn = layer_kinds(cfg)[0]
    k_mixer, k_ffn = jax.random.split(rng, 2)
    sub = {"ln1": init_rms_norm(cfg.d_model, dtype),
           "mixer": _init_mixer(mixer, k_mixer, cfg, dtype)}
    if ffn != "none":
        sub["ln2"] = init_rms_norm(cfg.d_model, dtype)
        sub["ffn"] = _init_ffn(ffn, k_ffn, cfg, dtype)
    return {"sub0": sub}


def init_stacked_blocks(rng, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    if not is_mixed(cfg):
        rngs = jax.random.split(rng, cfg.num_blocks)
        return jax.vmap(lambda r: init_block(r, cfg, dtype))(rngs)
    kinds = layer_kinds(cfg)
    L, d = cfg.num_layers, cfg.d_model
    out = {"ln1": {"scale": jnp.ones((L, d), dtype)},
           "ln2": {"scale": jnp.ones((L, d), dtype)}}
    mixers = [m for m, _ in kinds]
    ffns = [f for _, f in kinds]
    groups = ([(k, "mixer", _init_mixer, mixers.count(k))
               for k in sorted(set(mixers))]
              + [(k, "ffn", _init_ffn, ffns.count(k)) for k in sorted(set(ffns))])
    keys = jax.random.split(rng, len(groups))
    for key, (kind, part, init, n) in zip(keys, groups):
        out[kind] = {part: jax.vmap(lambda r: init(kind, r, cfg, dtype))(
            jax.random.split(key, n))}
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _mixer_step(kind: str, p, cfg: ModelConfig, x, h, positions):
    """``x`` plus the mixer's branch over ``h = LN(x)``."""
    # Scope names label the mixer's and FFN's operations in the HLO
    # metadata, for per-scope device time.
    if kind == "attn":
        with jax.named_scope("attention"):
            return _residual(cfg, x, attn_lib.attention_forward(
                p, cfg, h, positions))
    with jax.named_scope("ssd"):
        return _residual(cfg, x, mamba_lib.mamba_forward(p, cfg, h))


def _apply_ffn(p, ln2_scale, cfg: ModelConfig, ffn_kind: str, x):
    """Returns (delta, stats)."""
    if ffn_kind == "none":
        return None, ZERO_STATS
    h = rms_norm(x, ln2_scale, cfg.rms_eps)
    if ffn_kind == "moe":
        y, st = moe_lib.moe_forward(p, cfg.moe, h)
        return y, dict(aux_loss=st.aux_loss, router_z=st.router_z,
                       dropped_frac=st.dropped_frac)
    return mlp(p, h), ZERO_STATS


def _ffn_step(kind: str, p, ln2_scale, cfg: ModelConfig, x):
    """``x`` plus the FFN's branch; returns (x, stats)."""
    with jax.named_scope("moe" if kind == "moe" else "mlp"):
        delta, st = _apply_ffn(p, ln2_scale, cfg, kind, x)
    if delta is not None:
        x = _residual(cfg, x, delta)
    return x, st


def block_forward(params, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """One layer of a one-kind model (``params``: its ``sub0`` slice);
    returns (x, router stats)."""
    mixer, ffn = layer_kinds(cfg)[0]
    sub = params["sub0"]
    x = constrain(x)
    h = rms_norm(x, sub["ln1"]["scale"], cfg.rms_eps)
    x = _mixer_step(mixer, sub["mixer"], cfg, x, h, positions)
    ln2 = sub["ln2"]["scale"] if ffn != "none" else None
    return _ffn_step(ffn, sub.get("ffn"), ln2, cfg, x)


def _by_kind(kinds: Sequence[str], i, branches: Dict, *args):
    """``branches[kinds[i]](slot, *args)`` with ``slot`` layer ``i``'s
    index in its kind's stack.  A traced ``i`` switches on the kind."""
    slots = _slots(kinds)
    if isinstance(i, int):
        return branches[kinds[i]](slots[i], *args)
    names = sorted(set(kinds))
    slot = jnp.asarray(slots, jnp.int32)[i]
    if len(names) == 1:
        return branches[names[0]](slot, *args)
    index = jnp.asarray([names.index(k) for k in kinds], jnp.int32)[i]
    return jax.lax.switch(index, [branches[n] for n in names], slot, *args)


def _mixed_layer(blocks, cfg: ModelConfig, i, x, positions):
    kinds = layer_kinds(cfg)
    x = constrain(x)
    h = rms_norm(x, blocks["ln1"]["scale"][i], cfg.rms_eps)

    def mixer(kind):
        return lambda slot, x, h: _mixer_step(
            kind, _at(blocks[kind]["mixer"], slot), cfg, x, h, positions)

    def ffn(kind):
        def apply(slot, x):
            x, st = _ffn_step(kind, _at(blocks[kind]["ffn"], slot),
                              blocks["ln2"]["scale"][i], cfg, x)
            return x, {k: jnp.asarray(v, jnp.float32) for k, v in st.items()}
        return apply

    mixers = [m for m, _ in kinds]
    ffns = [f for _, f in kinds]
    x = _by_kind(mixers, i, {k: mixer(k) for k in set(mixers)}, x, h)
    return _by_kind(ffns, i, {k: ffn(k) for k in set(ffns)}, x)


def layer_forward(blocks, cfg: ModelConfig, i, x: jnp.ndarray,
                  positions: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """Layer ``i`` (an int or a traced scalar) of the stacked ``blocks``;
    returns (x, router stats)."""
    if is_mixed(cfg):
        return _mixed_layer(blocks, cfg, i, x, positions)
    return block_forward(_at(blocks, i), cfg, x, positions)


# -- caches -------------------------------------------------------------------


def _init_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int, dtype):
    if kind == "attn":
        return attn_lib.init_kv_cache(cfg, batch, max_len, dtype)
    return mamba_lib.init_mamba_cache(cfg, batch, dtype)


def init_stacked_cache(cfg: ModelConfig, batch: int, max_len: int,
                       dtype) -> Dict:
    """Decode caches stacked like the parameters: ``sub0 [L, ...]``, or
    one stack per mixer kind for a mixed model."""
    if not is_mixed(cfg):
        groups = {"sub0": (layer_kinds(cfg)[0][0], cfg.num_layers)}
    else:
        mixers = [m for m, _ in layer_kinds(cfg)]
        groups = {k: (k, mixers.count(k)) for k in set(mixers)}
    return {name: jax.tree.map(
        lambda a: jnp.zeros((n,) + a.shape, a.dtype),
        _init_cache(kind, cfg, batch, max_len, dtype))
        for name, (kind, n) in groups.items()}


def mixer_prefill(kind: str, p, cfg: ModelConfig, h, positions, cache):
    """The mixer's branch over the full sequence and its filled cache."""
    S = h.shape[1]
    if kind == "attn":
        q, k, v = attn_lib._project_qkv(p, cfg, h, positions)
        c = attn_lib._pick_chunk(S)
        o = attn_lib.flash_attention_jnp(
            q, k, v, causal=cfg.causal, window=cfg.sliding_window,
            chunk_q=c, chunk_k=c, scale=cfg.attention_scale)
        o = o.reshape(h.shape[0], S, cfg.num_heads * cfg.head_dim)
        return jnp.einsum("bsk,kd->bsd", o, p["wo"]), {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
    o, mc = mamba_prefill(p, cfg, h)
    return o, {"conv": mc["conv"].astype(cache["conv"].dtype),
               "ssm": mc["ssm"].astype(cache["ssm"].dtype)}


def mixer_decode(kind: str, p, cfg: ModelConfig, h, cache, index):
    if kind == "attn":
        return attn_lib.attention_decode(p, cfg, h, cache, index)
    return mamba_lib.mamba_decode(p, cfg, h, cache)


def block_prefill(params, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray, cache: Dict
                  ) -> Tuple[jnp.ndarray, Dict]:
    """One layer of a one-kind model over the full sequence, filling its
    decode cache (``params``, ``cache``: its ``sub0`` slices)."""
    mixer, ffn = layer_kinds(cfg)[0]
    sub = params["sub0"]
    h = rms_norm(x, sub["ln1"]["scale"], cfg.rms_eps)
    o, c = mixer_prefill(mixer, sub["mixer"], cfg, h, positions,
                          cache["sub0"])
    x = _residual(cfg, x, o)
    delta, _ = _apply_ffn(sub.get("ffn"), sub.get("ln2", {}).get("scale"),
                          cfg, ffn, x)
    if delta is not None:
        x = _residual(cfg, x, delta)
    return x, {"sub0": c}


def block_decode(params, cfg: ModelConfig, x: jnp.ndarray,
                 cache: Dict, index: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode through one layer of a one-kind model."""
    mixer, ffn = layer_kinds(cfg)[0]
    sub = params["sub0"]
    h = rms_norm(x, sub["ln1"]["scale"], cfg.rms_eps)
    o, c = mixer_decode(mixer, sub["mixer"], cfg, h, cache["sub0"], index)
    x = _residual(cfg, x, o)
    delta, _ = _apply_ffn(sub.get("ffn"), sub.get("ln2", {}).get("scale"),
                          cfg, ffn, x)
    if delta is not None:
        x = _residual(cfg, x, delta)
    return x, {"sub0": c}


def mixed_cached(blocks, cfg: ModelConfig, x: jnp.ndarray, cache: Dict,
                 mixer_fn) -> Tuple[jnp.ndarray, Dict]:
    """Every layer of a mixed model in order, each mixer through
    ``mixer_fn(kind, params, h, cache) -> (branch, cache)`` on its slot
    of its kind's cache stack (prefill or decode)."""
    kinds = layer_kinds(cfg)
    mslots = _slots([m for m, _ in kinds])
    fslots = _slots([f for _, f in kinds])
    cache = dict(cache)
    for i, (mixer, ffn) in enumerate(kinds):
        j = mslots[i]
        h = rms_norm(x, blocks["ln1"]["scale"][i], cfg.rms_eps)
        o, c = mixer_fn(mixer, _at(blocks[mixer]["mixer"], j), h,
                        _at(cache[mixer], j))
        cache[mixer] = jax.tree.map(lambda a, n: a.at[j].set(n),
                                    cache[mixer], c)
        x = _residual(cfg, x, o)
        delta, _ = _apply_ffn(_at(blocks[ffn]["ffn"], fslots[i]),
                              blocks["ln2"]["scale"][i], cfg, ffn, x)
        x = _residual(cfg, x, delta)
    return x, cache


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
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
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
