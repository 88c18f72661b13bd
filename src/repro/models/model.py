"""Model assembly: embeddings + stacked blocks (lax.scan) + head.

Public API (all functions jit-able, params are plain pytrees):

* ``init_params(rng, dtype)``
* ``forward(params, tokens|embeds)``            -> logits    (train path)
* ``loss(params, batch)``                       -> (scalar, metrics)
* ``init_cache(batch, max_len, dtype)``
* ``prefill(params, tokens|embeds, cache)``     -> (last logits, cache)
* ``decode_step(params, tokens, cache, index)`` -> (logits, cache)

Blocks (one per layer) are scanned over a stacked [num_blocks, ...]
parameter pytree — the same representation the pipeline runtime slices
per stage with dynamic boundaries.  A model whose layers mix kinds
(``blocks.is_mixed``) stacks each kind on its own and loops over layers
with :func:`blocks.layer_forward` instead.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as blk
from repro.models.layers import (
    cross_entropy,
    embed,
    init_embedding,
    init_rms_norm,
    init_unembed,
    rms_norm,
    unembed,
)


class Model:
    def __init__(self, cfg: ModelConfig, remat: bool = False,
                 unroll_blocks: bool = False):
        """``unroll_blocks``: python-loop over blocks instead of lax.scan.

        Used by the dry-run so per-block collectives/FLOPs appear
        ``num_blocks`` times in the HLO — XLA's cost analysis counts a
        while-loop body exactly once (verified), which would otherwise
        undercount everything inside the scan by L×.
        """
        self.cfg = cfg
        self.remat = remat
        self.unroll_blocks = unroll_blocks

    # -- init ----------------------------------------------------------------
    def init_params(self, rng, dtype=jnp.float32) -> Dict:
        cfg = self.cfg
        k_embed, k_blocks, k_head = jax.random.split(rng, 3)
        params = {
            "blocks": blk.init_stacked_blocks(k_blocks, cfg, dtype),
            "final_norm": init_rms_norm(cfg.d_model, dtype),
            "head": init_unembed(k_head, cfg.d_model, cfg.vocab_size, dtype),
        }
        # Even embedding-input models (VLM) keep a token table for decode.
        params["embed"] = init_embedding(k_embed, cfg.vocab_size,
                                         cfg.d_model, dtype)
        return params

    # -- shared block scan -----------------------------------------------------
    def _scan_blocks(self, params, x, positions):
        cfg = self.cfg

        def body(carry, bp):
            h, stats = carry
            h, st = blk.block_forward(bp, cfg, h, positions)
            stats = {k: stats[k] + st[k] for k in stats}
            return (h, stats), None

        if self.remat:
            body = jax.checkpoint(body)
        stats0 = {k: jnp.zeros((), jnp.float32) for k in blk.ZERO_STATS}
        if blk.is_mixed(cfg):
            def layer(i, carry):
                h, stats = carry
                h, st = blk.layer_forward(params["blocks"], cfg, i, h,
                                          positions)
                return h, {k: stats[k] + st[k] for k in stats}

            if self.remat:
                layer = jax.checkpoint(layer, static_argnums=(0,)) \
                    if self.unroll_blocks else jax.checkpoint(layer)
            if self.unroll_blocks:
                carry = (x, stats0)
                for i in range(cfg.num_blocks):
                    carry = layer(i, carry)
                return carry
            return jax.lax.fori_loop(0, cfg.num_blocks, layer, (x, stats0))
        if self.unroll_blocks:
            carry = (x, stats0)
            for i in range(cfg.num_blocks):
                bp = jax.tree.map(lambda p: p[i], params["blocks"])
                carry, _ = body(carry, bp)
            x, stats = carry
            return x, stats
        (x, stats), _ = jax.lax.scan(body, (x, stats0), params["blocks"])
        return x, stats

    def _embed_in(self, params, tokens: Optional[jnp.ndarray],
                  embeds: Optional[jnp.ndarray]) -> jnp.ndarray:
        if embeds is not None:
            return embeds
        return embed(params["embed"], tokens, self.cfg.embedding_multiplier)

    # -- train / encoder path -----------------------------------------------
    def forward(self, params, tokens: Optional[jnp.ndarray] = None,
                embeds: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict]:
        x = self._embed_in(params, tokens, embeds)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, stats = self._scan_blocks(params, x, positions)
        return self._logits(params, x), stats

    def _logits(self, params, x):
        x = rms_norm(x, params["final_norm"]["scale"], self.cfg.rms_eps)
        return unembed(params["head"], x, self.cfg.logits_scaling)

    def loss(self, params, batch: Dict) -> Tuple[jnp.ndarray, Dict]:
        """batch: {tokens|embeds, labels, [mask]}."""
        logits, stats = self.forward(
            params, batch.get("tokens"), batch.get("embeds"))
        ce = cross_entropy(logits, batch["labels"], batch.get("mask"))
        m = self.cfg.moe
        aux_coef = m.router_aux_coef if m is not None else 0.0
        total = ce + aux_coef * stats["aux_loss"] + 1e-4 * stats["router_z"]
        metrics = {"ce": ce, "aux_loss": stats["aux_loss"],
                   "router_z": stats["router_z"],
                   "dropped_frac": stats["dropped_frac"], "loss": total}
        return total, metrics

    # -- decode path -----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> Dict:
        return blk.init_stacked_cache(self.cfg, batch, max_len, dtype)

    def prefill(self, params, tokens: Optional[jnp.ndarray] = None,
                embeds: Optional[jnp.ndarray] = None,
                cache: Optional[Dict] = None) -> Tuple[jnp.ndarray, Dict]:
        """Full-sequence pass filling the cache; returns last-pos logits."""
        cfg = self.cfg
        x = self._embed_in(params, tokens, embeds)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        def body(h, bp_cache):
            bp, c = bp_cache
            h, new_c = blk.block_prefill(bp, cfg, h, positions, c)
            return h, new_c

        if self.remat:
            body = jax.checkpoint(body)
        if blk.is_mixed(cfg):
            x, new_cache = blk.mixed_cached(
                params["blocks"], cfg, x, cache,
                lambda kind, p, h, c: blk.mixer_prefill(
                    kind, p, cfg, h, positions, c))
        elif self.unroll_blocks:
            new_caches = []
            for i in range(cfg.num_blocks):
                bp_c = jax.tree.map(lambda p: p[i], (params["blocks"], cache))
                x, nc = body(x, bp_c)
                new_caches.append(nc)
            new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
        else:
            x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
        return self._logits(params, x[:, -1:]), new_cache

    def decode_step(self, params, tokens: jnp.ndarray, cache: Dict,
                    index: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
        """tokens: [B, 1] -> (logits [B, 1, V], updated cache)."""
        cfg = self.cfg
        x = embed(params["embed"], tokens, cfg.embedding_multiplier)

        def body(h, bp_cache):
            bp, c = bp_cache
            h, new_c = blk.block_decode(bp, cfg, h, c, index)
            return h, new_c

        if blk.is_mixed(cfg):
            x, new_cache = blk.mixed_cached(
                params["blocks"], cfg, x, cache,
                lambda kind, p, h, c: blk.mixer_decode(
                    kind, p, cfg, h, c, index))
        elif self.unroll_blocks:
            new_caches = []
            for i in range(cfg.num_blocks):
                bp_c = jax.tree.map(lambda p: p[i], (params["blocks"], cache))
                x, nc = body(x, bp_c)
                new_caches.append(nc)
            new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
        else:
            x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
        return self._logits(params, x), new_cache


# ---------------------------------------------------------------------------
# Step factories (jit-able top-level entry points)
# ---------------------------------------------------------------------------


def make_forward_fn(cfg: ModelConfig, remat: bool = False):
    model = Model(cfg, remat=remat)

    @functools.partial(jax.jit, static_argnums=())
    def fwd(params, batch):
        return model.forward(params, batch.get("tokens"), batch.get("embeds"))

    return fwd
