"""Recompile-free pipeline-stage executor (DESIGN.md §2).

The model's blocks (one per layer) are stacked along a leading
``[num_blocks, ...]`` axis, or by kind for a model that mixes attention
and Mamba-2 layers (``models/blocks.py``); a pipeline stage executes
blocks ``[lo, hi)`` via ``lax.fori_loop`` with *traced* bounds, so the
ODIN rebalancer can move blocks between stages one layer at a time
without triggering any recompilation — trial configurations run at full
speed (beyond-paper: the paper processes queries serially during
rebalancing; its exhaustive-search alternative took 42.5 minutes).

This executor runs every stage on the host device(s) sequentially and
*measures* per-stage wall time — exactly the signal ODIN consumes.  The
SPMD multi-stage schedule (each stage on its own mesh slice) lives in
``repro.pipeline.spmd``.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import blocks as blk
from repro.models.layers import embed, rms_norm, unembed
from repro.telemetry.spans import span
# Canonical home is the typed serving-error hierarchy
# (repro.util.errors); re-exported here for backward compatibility.
from repro.util.errors import MixedSequenceLengthError  # noqa: F401


def stage_bounds(config: Sequence[int]) -> List[tuple]:
    """[(lo, hi)] block ranges per stage for a layer-count config."""
    out, lo = [], 0
    for c in config:
        out.append((lo, lo + c))
        lo += c
    return out


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << (max(int(n), 1) - 1).bit_length()


def wait_ready(out: jax.Array, name: str, **meta: int) -> float:
    """Wait for ``out`` inside span ``name`` (one host sync); returns
    the host clock when it was seen ready."""
    with span(name, syncs=1, **meta):
        out.block_until_ready()
    return time.perf_counter()


class _ReadyClock:
    """Stage times from the host clock at each output's readiness.

    :meth:`queue` takes an output already dispatched and only then waits
    for the one queued before it, so the host never waits with the
    device's queue empty.  An output whose span metadata name a
    ``stage`` is timed: the clock at its readiness less the clock at the
    previous readiness (or at the clock's start).
    """

    def __init__(self, lo_stage: int, n: int):
        self.times = np.zeros(n)
        self._lo = lo_stage
        self._pending = None
        self._t = time.perf_counter()

    def queue(self, out: jax.Array, name: str, **meta: int) -> None:
        prev, self._pending = self._pending, (out, name, meta)
        if prev is not None:
            self._settle(*prev)

    def drain(self) -> None:
        """Wait for the output queued last."""
        if self._pending is not None:
            self._settle(*self._pending)
            self._pending = None

    def record(self, stage: int, seconds: float) -> None:
        """A stage the caller timed itself, ending now."""
        self.times[stage - self._lo] = seconds
        self._t = time.perf_counter()

    def _settle(self, out: jax.Array, name: str, meta: dict) -> None:
        t = wait_ready(out, name, **meta)
        if "stage" in meta:
            self.times[meta["stage"] - self._lo] = t - self._t
        self._t = t


class LocalPipelineExecutor:
    """Executes a stage-partitioned model, timing each stage.

    One jitted ``stage_fn(params, x, positions, lo, hi)`` serves *all*
    stages and *all* configurations — bounds are runtime arguments.
    """

    def __init__(self, cfg: ModelConfig, params: Dict):
        self.cfg = cfg
        self.params = params
        cfg_ = cfg

        # The named scopes label each program's operations in the
        # compiled HLO's metadata (op_name), for per-scope device time.
        @jax.jit
        def stage_fn(params, x, positions, lo, hi):
            def body(i, h):
                h, _ = blk.layer_forward(params["blocks"], cfg_, i, h,
                                         positions)
                return h
            with jax.named_scope("stage"):
                return jax.lax.fori_loop(lo, hi, body, x)

        @jax.jit
        def embed_fn(params, tokens):
            with jax.named_scope("embed"):
                return embed(params["embed"], tokens,
                             cfg_.embedding_multiplier)

        @jax.jit
        def head_fn(params, x):
            with jax.named_scope("head"):
                x = rms_norm(x, params["final_norm"]["scale"], cfg_.rms_eps)
                return unembed(params["head"], x, cfg_.logits_scaling)

        self._stage_fn = stage_fn
        self._embed_fn = embed_fn
        self._head_fn = head_fn
        self._warmed = set()       # (batch, seq) shapes already compiled
        # Block edges 0..L as committed device scalars, once: every
        # stage's bounds are a pair of them.
        self._edges = [jnp.int32(i) for i in range(cfg.num_blocks + 1)]
        jax.block_until_ready(self._edges)
        # Attention layers before each edge, for the stage spans' counts.
        self._attn_before = np.cumsum(
            [0] + [cfg.layer_kind(i) == "attn"
                   for i in range(cfg.num_blocks)])
        self._pos = {}             # (batch, seq) -> positions

    # -- warmup ---------------------------------------------------------------
    def warmup(self, batch: int, seq: int) -> None:
        x = jnp.zeros((batch, seq), jnp.int32)
        self.run_query(x, [self.cfg.num_blocks])
        self._warmed.add((batch, seq))

    def ensure_warm(self, batch: int, seq: int) -> None:
        """Compile the (batch, seq) input shape if not yet seen.

        The executor is recompile-free across *configurations* (stage
        bounds are runtime arguments), but XLA still specializes on the
        input shape — so a batched dispatch must never pay (or measure)
        a first-shape compile inside the serving loop."""
        if (batch, seq) not in self._warmed:
            self.warmup(batch, seq)

    def warm_buckets(self, seq_buckets: Sequence[int],
                     max_batch: int) -> None:
        """Pre-compile exactly the length-bucketed dispatch shapes.

        Bucketed dispatch pads every batch to a power-of-two row count
        and every query to its length-bucket edge, so the full shape set
        is ``{1, 2, 4, .., next_pow2(max_batch)} x seq_buckets`` — a
        small closed set, keeping ``_warmed`` bounded however many
        distinct raw ``(batch, seq)`` combinations the traffic offers.
        """
        rows, r = [], 1
        cap = next_pow2(max_batch)
        while r <= cap:
            rows.append(r)
            r *= 2
        for seq in seq_buckets:
            for b in rows:
                self.ensure_warm(b, int(seq))

    # -- execution --------------------------------------------------------------
    def _positions(self, batch: int, seq: int) -> jnp.ndarray:
        """``[batch, seq]`` token positions, made once per shape."""
        if (batch, seq) not in self._pos:
            self._pos[batch, seq] = jnp.broadcast_to(
                jnp.arange(seq, dtype=jnp.int32), (batch, seq))
        return self._pos[batch, seq]

    def _device_bounds(self, config: Sequence[int]) -> List[tuple]:
        """Stage bounds as committed device scalars.

        Each bound is one of the block edges committed when the executor
        was built, so no query puts or waits for a bound, and no
        host->device transfer lands inside a stage-time measurement.
        """
        with span("executor.bounds", syncs=0):
            return [(self._edges[lo], self._edges[hi])
                    for lo, hi in stage_bounds(config)]

    def embed_tokens(self, tokens: jnp.ndarray) -> tuple:
        """Embed ``[B, S]`` tokens -> (hidden ``[B, S, D]``, positions).

        Blocks until the embedding is on device so the first stage's
        measured time never includes the embed dispatch.
        """
        with span("executor.embed", syncs=1):
            x = self._embed_fn(self.params, tokens)
            x.block_until_ready()
        return x, self._positions(*tokens.shape)

    def _chain(self, x: jnp.ndarray, positions: jnp.ndarray,
               config: Sequence[int], lo_stage: int, hi_stage: int,
               slowdowns: Optional[Sequence[float]], bounds: List[tuple],
               tokens: Optional[jnp.ndarray] = None,
               head: bool = False) -> tuple:
        """Run [embedding of ``tokens``], stages ``[lo_stage, hi_stage)``
        and [the head] back to back.

        Each program is dispatched before the host waits for the one
        before it, so the device always has the next program queued and
        no host round trip idles it.  A stage's time is the host clock
        at its output's readiness less that at the previous output's
        (the embedding's, or the chain's start, where ``x`` is ready).

        A stage slowed by ``slowdowns`` keeps the synchronous path: it
        is dispatched once the previous output is ready, timed from its
        dispatch to its readiness, then the host sleeps ``(factor - 1)``
        times that, and the next program is dispatched only after the
        sleep, so the device idles through the emulated interference.
        """
        clock = _ReadyClock(lo_stage, hi_stage - lo_stage)
        if tokens is not None:
            with span("executor.embed", syncs=0):
                x = self._embed_fn(self.params, tokens)
            clock.queue(x, "executor.embed")
        edges = stage_bounds(config)
        for s in range(lo_stage, hi_stage):
            lo, hi = bounds[s]
            meta = self._stage_meta(s, *edges[s])
            factor = 1.0 if slowdowns is None else float(slowdowns[s])
            if factor <= 1.0:
                with span("executor.stage", syncs=0, **meta):
                    x = self._stage_fn(self.params, x, positions, lo, hi)
                clock.queue(x, "executor.stage", **meta)
                continue
            clock.drain()
            with span("executor.stage", syncs=1, **meta):
                t0 = time.perf_counter()
                x = self._stage_fn(self.params, x, positions, lo, hi)
                x.block_until_ready()
                dt = time.perf_counter() - t0
            extra = dt * (factor - 1.0)
            with span("executor.interference", stage=s,
                      factor_pct=int(round(100 * factor))):
                time.sleep(extra)
            clock.record(s, dt + extra)
        if head:
            x = self.head(x)
            clock.queue(x, "executor.head")
        clock.drain()
        return x, clock.times

    def _stage_meta(self, stage: int, lo: int, hi: int) -> Dict[str, int]:
        """An ``executor.stage`` span's metadata: the stage, its layer
        range ``[lo, hi)`` and how many of its layers are attention and
        Mamba-2."""
        attn = int(self._attn_before[hi] - self._attn_before[lo])
        return {"stage": stage, "blocks": hi - lo, "lo": lo, "hi": hi,
                "attn": attn, "mamba": hi - lo - attn}

    def run_stages(self, x: jnp.ndarray, positions: jnp.ndarray,
                   config: Sequence[int], lo_stage: int, hi_stage: int,
                   slowdowns: Optional[Sequence[float]] = None,
                   bounds: Optional[List[tuple]] = None) -> tuple:
        """Run stages ``[lo_stage, hi_stage)`` of ``config`` over ``x``.

        The stage-granular entry point for continuous batching: a batch
        can stop at any stage boundary, absorb newly arrived (embedded +
        caught-up) queries along the batch axis, and resume — all with
        the same jitted ``stage_fn``, since stage bounds and the batch
        dimension are runtime arguments (no recompile).

        ``x`` must be ready; the returned ``x`` is ready too.  Returns
        ``(x, times)`` where ``times[s]`` is the measured wall time of
        stage ``lo_stage + s``, timed like :meth:`run_query`'s (the
        first from its dispatch).  ``bounds`` accepts the
        :meth:`_device_bounds` result.
        """
        if bounds is None:
            bounds = self._device_bounds(config)
        return self._chain(x, positions, config, lo_stage, hi_stage,
                           slowdowns, bounds)

    def head(self, x: jnp.ndarray) -> jnp.ndarray:
        """Dispatch final norm + unembed; the caller waits for the
        logits (:func:`wait_ready` with ``"executor.head"``)."""
        with span("executor.head", syncs=0):
            return self._head_fn(self.params, x)

    def run_query(self, tokens: jnp.ndarray, config: Sequence[int],
                  slowdowns: Optional[Sequence[float]] = None
                  ) -> tuple:
        """Run one query through the pipeline of ``config``.

        Returns (logits, stage_times_seconds ndarray), the logits ready.
        The embedding, every stage and the head run back to back on the
        device (:meth:`_chain`).  ``slowdowns`` emulates co-located
        interference per EP by stretching the measured stage time
        (sleep), physically delaying the pipeline — the scheduler only
        ever sees measured times.
        """
        return self._chain(None, self._positions(*tokens.shape), config,
                           0, len(config), slowdowns,
                           self._device_bounds(config), tokens=tokens,
                           head=True)

    def run_batch(self, queries: Sequence[jnp.ndarray],
                  config: Sequence[int],
                  slowdowns: Optional[Sequence[float]] = None
                  ) -> tuple:
        """Run a stacked batch of queries through the pipeline once.

        ``queries`` are ``[B_i, S]`` token arrays with one shared
        sequence length; they are concatenated along the batch axis and
        every stage executes a single time over the stacked batch — the
        same jitted ``stage_fn`` (the batch dimension was always a
        runtime size), so a burst of B queries pays one set of stage
        dispatches + device syncs instead of B of them.

        Returns (logits ``[sum(B_i), S, V]``, stage_times ndarray).
        Stage times cover the whole batch; per-query attribution is the
        caller's policy (the serving engine divides by the batch size).

        A single-query batch is forwarded as-is (no concat, no copy);
        mixed sequence lengths raise :class:`MixedSequenceLengthError`
        naming every query's length.
        """
        if len(queries) == 0:
            raise ValueError("run_batch needs at least one query")
        if len(queries) == 1:
            tokens = queries[0]
        else:
            lengths = [int(t.shape[-1]) for t in queries]
            if len(set(lengths)) != 1:
                raise MixedSequenceLengthError(lengths)
            tokens = jnp.concatenate(list(queries), axis=0)
        return self.run_query(tokens, config, slowdowns=slowdowns)

    def measure_block_times(self, tokens: jnp.ndarray,
                            repeats: int = 3) -> np.ndarray:
        """Per-block clean execution times (database column 0)."""
        positions = self._positions(*tokens.shape)
        x = self._embed_fn(self.params, tokens)
        L = self.cfg.num_blocks
        edges = self._edges
        times = np.zeros((repeats, L))
        for r in range(repeats):
            h = x
            for i in range(L):
                h.block_until_ready()
                t0 = time.perf_counter()
                h = self._stage_fn(self.params, h, positions,
                                   edges[i], edges[i + 1])
                h.block_until_ready()
                times[r, i] = time.perf_counter() - t0
        return times.min(axis=0)


class MeasuredTimeSource:
    """StageTimeSource over real measured per-block times + live scenarios.

    Bridges the executor world to the ODIN/LLS controllers: stage time =
    sum of its blocks' measured clean times × the EP's current slowdown.
    Polled on every exploration trial, so the per-stage reduction is one
    ``np.add.reduceat`` over the config's block offsets instead of a
    Python loop over stages.

    With a :class:`~repro.core.mesh.MeshSpec` attached the source
    additionally models mesh-sliced stages (docs/SHARDING.md): the
    measured compute time divides by the stage's device count and a
    modeled collective term is added via
    :func:`~repro.core.mesh.mesh_stage_times` — the same cost model the
    simulator uses, so a live scheduler reasons over (boundary, slice)
    moves from measured data.  ``assignment`` is the committed slice
    vector (the runtime keeps it synced); ``coll_factor`` is the live
    collective-contention estimate (1.0 when quiet).  ``mesh=None``
    (the default) touches none of this — byte-identical behavior to the
    pre-mesh source.
    """

    def __init__(self, block_times: np.ndarray, slowdowns: np.ndarray,
                 mesh=None, coll_times: Optional[np.ndarray] = None,
                 assignment: Optional[Sequence[int]] = None,
                 coll_factor: float = 1.0):
        self.block_times = np.asarray(block_times, float)
        self.slowdowns = np.asarray(slowdowns, float)  # per EP
        self.mesh = mesh  # MeshSpec or None
        self.coll_times = (np.asarray(coll_times, float)
                           if coll_times is not None
                           else (mesh.layer_costs(len(self.block_times))
                                 if mesh is not None else None))
        self.assignment = (list(assignment) if assignment is not None
                           else None)
        self.coll_factor = float(coll_factor)

    def _compute_times(self, config: Sequence[int]) -> np.ndarray:
        counts = np.asarray(config, dtype=np.int64)
        out = np.zeros(len(counts))
        nz = counts > 0
        if nz.any():
            starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
            # reduceat over the offsets of non-empty stages only: each
            # segment then ends exactly at the next non-empty stage's
            # start (empty stages contribute no blocks and stay 0).
            out[nz] = np.add.reduceat(self.block_times, starts[nz])
        return out * self.slowdowns

    def stage_times(self, config: Sequence[int],
                    assignment: Optional[Sequence[int]] = None
                    ) -> np.ndarray:
        compute = self._compute_times(config)
        if self.mesh is None:
            return compute
        a = assignment if assignment is not None else self.assignment
        if a is None:
            return compute
        from repro.core.mesh import mesh_stage_times
        return mesh_stage_times(compute, config, a, self.mesh,
                                self.coll_factor,
                                layer_costs=self.coll_times)

    def collective_frac(self, config: Sequence[int],
                        assignment: Optional[Sequence[int]] = None
                        ) -> float:
        """Bottleneck stage's modeled collective share (the live
        ``collective_frac`` trace column); 0.0 unsharded."""
        if self.mesh is None:
            return 0.0
        a = assignment if assignment is not None else self.assignment
        if a is None:
            return 0.0
        from repro.core.mesh import collective_frac as _frac
        return _frac(self._compute_times(config), config, a, self.mesh,
                     self.coll_factor, layer_costs=self.coll_times)
