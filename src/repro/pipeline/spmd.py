"""SPMD pipeline-parallel execution (bind-to-stage on mesh slices).

Maps the paper's execution places onto mesh slices: a ``stage`` mesh axis
partitions the chips into N execution places; each holds a *padded* tile
of the stacked block parameters (``[cap, ...]`` per stage, cap ≥ the
largest stage ODIN may create).  The live block count per stage is a
runtime argument, so ODIN rebalancing = a cheap weight reshuffle + new
count vector — never a recompile.

The schedule is GPipe-style fill/drain over M microbatches with
activations handed to the next stage via ``jax.lax.ppermute`` each step;
empty stages (count 0) forward activations untouched, which is exactly
the paper's "pipeline may shorten by one stage" semantics.

The remaining mesh axes (e.g. ``model``) shard each stage's computation
(operator parallelism *within* an execution place, paper §2).
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import blocks as blk


def stage_mesh(num_stages: int, *, model_parallel: int = 1):
    """The SPMD pipeline's mesh, built through the single shared
    constructor in :mod:`repro.launch.mesh` (this module used to build
    its own; docs/SHARDING.md).  ``stage`` partitions the chips into
    execution places; ``model`` is operator parallelism within one."""
    from repro.launch.mesh import make_stage_mesh
    return make_stage_mesh(num_stages, model_parallel=model_parallel)


def pack_stage_params(stacked_blocks: Dict, config: Sequence[int],
                      cap: int) -> Dict:
    """Repack [L, ...] stacked blocks into [num_stages, cap, ...] tiles.

    Stage s's tile holds its blocks [lo_s, hi_s) in slots [0, count_s);
    the padding slots keep whatever block data fills them (they are never
    executed).  On rebalance this is re-materialized — the weight-
    migration cost the paper pays when moving layers between EPs.
    """
    L = jax.tree.leaves(stacked_blocks)[0].shape[0]
    n = len(config)

    def pack(p):
        tiles = []
        lo = 0
        for c in config:
            idx = (jnp.arange(cap) + lo).clip(0, L - 1)
            tiles.append(p[idx])
            lo += c
        return jnp.stack(tiles)  # [n, cap, ...]

    return jax.tree.map(pack, stacked_blocks)


def make_pipeline_fn(cfg: ModelConfig, mesh, *, stage_axis: str = "stage",
                     num_microbatches: int = 4, cap: int):
    """Build the jit-able pipelined forward.

    Signature: fn(stage_params, counts, inputs) -> outputs
      stage_params: [n_stages, cap, ...] pytree (sharded over stage_axis)
      counts:       [n_stages] int32 live block counts
      inputs:       [M, mb, S, d] embedded microbatches (replicated)
      outputs:      [M, mb, S, d] final hidden states (replicated)
    """
    if blk.is_mixed(cfg):
        raise NotImplementedError(
            f"{cfg.name}: the SPMD schedule packs one stack of blocks; a "
            "model whose layers mix kinds is served by "
            "LocalPipelineExecutor")
    n_stages = mesh.shape[stage_axis]
    M = num_microbatches

    def stage_compute(params_local, x, positions, count):
        def body(i, h):
            bp = jax.tree.map(lambda p: p[i], params_local)
            h, _ = blk.block_forward(bp, cfg, h, positions)
            return h
        return jax.lax.fori_loop(0, count, body, x)

    def pipeline(stage_params, counts, inputs):
        # local views: stage_params [1, cap, ...]; counts [1]; inputs full
        sp = jax.tree.map(lambda p: p[0], stage_params)
        stage_id = jax.lax.axis_index(stage_axis)
        count = counts[stage_id]
        _, mb, S, d = inputs.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))
        is_last = stage_id == n_stages - 1

        T = n_stages + M - 1
        x0 = jnp.zeros((mb, S, d), inputs.dtype)
        out0 = jnp.zeros((M, mb, S, d), inputs.dtype)

        def step(t, carry):
            x_cur, outputs = carry
            mb_idx = t - stage_id
            active = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 pulls microbatch t from the input queue
            feed = jax.lax.dynamic_index_in_dim(
                inputs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            x_in = jnp.where(stage_id == 0, feed, x_cur)
            y = stage_compute(sp, x_in, positions, count)
            y = jnp.where(active, y, x_in)
            # hand activations to the next stage
            x_next = jax.lax.ppermute(
                y, stage_axis, [(i, i + 1) for i in range(n_stages - 1)])
            # last stage commits its finished microbatch
            upd = jax.lax.dynamic_update_index_in_dim(
                outputs, y, jnp.clip(mb_idx, 0, M - 1), axis=0)
            outputs = jnp.where(is_last & active, upd, outputs)
            return (x_next, outputs)

        _, outputs = jax.lax.fori_loop(0, T, step, (x0, out0))
        # broadcast the last stage's buffer to every stage
        mask = jnp.where(is_last, 1.0, 0.0).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, stage_axis)

    # model-parallel sub-sharding of the per-stage tiles is delegated to
    # pjit on the caller side; the shard_map here only owns stage_axis.
    fn = jax.shard_map(
        pipeline, mesh=mesh,
        in_specs=(P(stage_axis), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def pipelined_forward(cfg: ModelConfig, mesh, stacked_blocks: Dict,
                      config: Sequence[int], inputs: jnp.ndarray, *,
                      cap: int, stage_axis: str = "stage",
                      num_microbatches: int = 4) -> jnp.ndarray:
    """Convenience wrapper: pack + run.  inputs: [M, mb, S, d] embedded."""
    stage_params = pack_stage_params(stacked_blocks, config, cap)
    counts = jnp.asarray(list(config), jnp.int32)
    fn = make_pipeline_fn(cfg, mesh, stage_axis=stage_axis,
                          num_microbatches=num_microbatches, cap=cap)
    return fn(stage_params, counts, inputs)


class SpmdPipelineExecutor:
    """Physical sharded-stage execution — the SPMD counterpart of
    :class:`repro.pipeline.executor.LocalPipelineExecutor`.

    Each pipeline stage owns one slice of a :func:`stage_mesh`; a query
    runs embed → GPipe-schedule stages (``ppermute`` hand-offs between
    slices) → head, and ODIN rebalancing stays recompile-free because
    the live block counts are runtime arguments.  Requires
    ``jax.device_count() >= num_stages`` (guard call sites; the serving
    loop's scheduler-side mesh *model* in
    :class:`~repro.pipeline.executor.MeasuredTimeSource` needs no
    devices and is the default — docs/SHARDING.md).
    """

    def __init__(self, cfg: ModelConfig, params: Dict, num_stages: int, *,
                 cap: int = 0, model_parallel: int = 1,
                 num_microbatches: int = 1):
        if jax.device_count() < num_stages * model_parallel:
            raise ValueError(
                f"{num_stages}x{model_parallel} mesh needs "
                f">= {num_stages * model_parallel} devices, have "
                f"{jax.device_count()}")
        self.cfg = cfg
        self.params = params
        self.mesh = stage_mesh(num_stages, model_parallel=model_parallel)
        self.cap = int(cap) if cap else cfg.num_blocks
        self.M = int(num_microbatches)
        self._fn = make_pipeline_fn(cfg, self.mesh,
                                    num_microbatches=self.M, cap=self.cap)

    def run_query(self, tokens: jnp.ndarray,
                  config: Sequence[int]) -> jnp.ndarray:
        """Run ``[B, S]`` tokens through the sharded pipeline of
        ``config``; returns logits ``[B, S, V]``.  ``B`` is padded up to
        a multiple of the microbatch count, padding rows dropped."""
        from repro.models.layers import embed, rms_norm, unembed
        B, S = tokens.shape
        mb = -(-B // self.M)  # rows per microbatch, padded up
        if mb * self.M > B:
            tokens = jnp.concatenate(
                [tokens, jnp.zeros((mb * self.M - B, S), tokens.dtype)])
        x = embed(self.params["embed"], tokens,
                  self.cfg.embedding_multiplier)
        inputs = x.reshape(self.M, mb, S, -1)
        stage_params = pack_stage_params(self.params["blocks"], config,
                                         self.cap)
        counts = jnp.asarray(list(config), jnp.int32)
        out = self._fn(stage_params, counts, inputs)
        h = out.reshape(mb * self.M, S, -1)[:B]
        h = rms_norm(h, self.params["final_norm"]["scale"],
                     self.cfg.rms_eps)
        return unembed(self.params["head"], h, self.cfg.logits_scaling)
