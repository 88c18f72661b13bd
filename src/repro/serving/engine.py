"""Live serving engine: scheduler policies against *measured* stage times.

This is the end-to-end integration of the paper's technique: real JAX
model execution through the recompile-free pipeline executor, per-stage
wall-clock monitoring, online interference detection, and stepwise
rebalancing — one exploration trial per (serially processed) query.

The detect → explore → commit state machine is the same
:class:`~repro.schedulers.runtime.RebalanceRuntime` the simulator
drives, and the per-query loop itself is the same
:func:`repro.workloads.run_pipeline`: the engine only supplies physical
time (a :class:`~repro.pipeline.executor.MeasuredTimeSource` built from
the EMA of measured per-block times) where the simulator supplies
database lookups.  Any registered policy name — or a custom
:class:`~repro.schedulers.base.SchedulerPolicy` instance — plugs in, as
does any registered workload (closed-loop by default; ``poisson`` /
``bursty`` / ``trace`` for open-loop runs with queueing accounting in
wall-clock seconds).

Detection runs at the shared
:data:`repro.schedulers.DEFAULT_REL_THRESHOLD` in the detector's
EMA/hysteresis mode (measured times jitter query-to-query; see
``repro.schedulers.defaults``).

Interference is injected as per-EP slowdown factors (emulating co-located
tenants; the measured-database builder in tools/ uses real co-running
stressor processes instead).

``serve(..., max_batch=N)`` enables batched serving: open-loop arrivals
that queued up behind the pipeline are stacked and executed through
``LocalPipelineExecutor.run_batch`` — one set of stage dispatches per
burst — while the detect → explore → commit machinery still observes
every query (docs/WORKLOADS.md "Batching & the fast path").

``serve(..., batching="continuous", buckets=...)`` enables continuous
batching on top: length-bucketed formed dispatches run stage by stage
through the executor's stage-granular ``run_stages``, and a query that
arrives while a same-bucket batch is in flight joins it at the next
pipeline-stage boundary — one fused catch-up launch instead of waiting
out the full group-synchronous drain (docs/WORKLOADS.md "Continuous
batching & length buckets").
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Sequence, Union

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.mesh import (
    balanced_assignment,
    collective_frac as _mesh_collective_frac,
    mesh_stage_times,
    resolve_mesh,
)
from repro.core.pipeline_state import balanced_config, throughput
from repro.pipeline.executor import (
    LocalPipelineExecutor,
    MeasuredTimeSource,
    next_pow2,
    wait_ready,
)
from repro.schedulers.base import SchedulerPolicy
from repro.schedulers.defaults import DEFAULT_ALPHA, MEASURED_DETECTOR_MODE
from repro.schedulers.registry import make_scheduler
from repro.util.errors import QueryError
from repro.schedulers.runtime import RebalanceRuntime, RuntimeStep
from repro.telemetry.spans import span
from repro.workloads import (
    BatchRecord,
    DispatchRecord,
    PipelineTrace,
    QueryRecord,
    Workload,
    resolve_batching,
)
from repro.workloads.runner import _run_pipeline_impl

#: Deprecated alias — ``serve()`` now returns the unified
#: :class:`repro.workloads.PipelineTrace` (same summary keys plus the
#: queueing/SLO surface the simulator already had).
ServeMetrics = PipelineTrace


class _LiveQueryExecutor:
    """Engine-side :class:`~repro.workloads.QueryExecutor`.

    Each query runs for real through the
    :class:`~repro.pipeline.executor.LocalPipelineExecutor`; the
    scheduler runtime is polled with a
    :class:`~repro.pipeline.executor.MeasuredTimeSource` over the
    engine's online per-block time estimates.  Until the first query has
    been measured there are no estimates to reason over, so
    ``begin_query`` returns ``None`` and the query runs steady.

    With ``max_batch > 1`` the executor opts into the run loop's real
    batching (``batch_mode = "batch"``): queries that have already
    arrived are drained into one stacked
    :meth:`~repro.pipeline.executor.LocalPipelineExecutor.run_batch`
    call — one set of stage dispatches + device syncs per burst instead
    of one per query.  The scheduler is still polled per query (the
    EMA/hysteresis detector must see every observation), so
    rebalance/trial accounting stays aligned with the unbatched run.
    """

    def __init__(self, engine: "ServingEngine",
                 queries: Sequence[jnp.ndarray], slowdown_schedule,
                 max_batch: int = 1):
        self.engine = engine
        self.queries = queries
        self.schedule = slowdown_schedule
        self.max_batch = max(1, int(max_batch))
        self._slow: Optional[np.ndarray] = None
        self._cf = 1.0  # live collective-contention factor (mesh runs)
        # Batched-dispatch state (the run loop's configure_batching
        # hook fills these in when a BatchFormer is attached).
        self.former = None
        self._lengths: Optional[np.ndarray] = None
        self._padded: Optional[np.ndarray] = None

    @property
    def batch_mode(self) -> Optional[str]:
        return "batch" if self.max_batch > 1 else None

    @property
    def max_chunk(self) -> int:
        return self.max_batch

    def begin_query(self, q: int) -> Optional[MeasuredTimeSource]:
        self._slow = np.asarray(self.schedule(q), float)
        self._cf = self.engine._coll_factor_at(q)
        if self.engine._block_times is None:
            return None
        return self.engine._measured_source(self._slow, self._cf)

    def steady_horizon(self, q: int) -> int:
        """Constant-interference run length from ``q``: a batch must
        share one slowdown vector (a schedule edge ends the chunk), one
        collective-contention factor when a mesh is armed, and one
        dispatch shape (stacked rows need one shared sequence
        length — a length change ends the chunk; with buckets attached
        the cut falls at bucket-edge changes instead)."""
        base = np.asarray(self.schedule(q), float)
        cf = self.engine._coll_factor_at(q)
        width = self._width(q)
        n = 1
        while (n < self.max_batch and q + n < len(self.queries)
               and self._width(q + n) == width
               and self.engine._coll_factor_at(q + n) == cf
               and np.array_equal(np.asarray(self.schedule(q + n), float),
                                  base)):
            n += 1
        return n

    def _width(self, q: int) -> int:
        """Sequence length query ``q`` dispatches at (bucket edge when
        buckets are attached, raw length otherwise)."""
        if self._padded is not None:
            return int(self._padded[q])
        return int(self.queries[q].shape[-1])

    # -- batched dispatch (run-loop hooks) --------------------------------

    def configure_batching(self, former, lengths, padded) -> None:
        """Run-loop hook (:func:`repro.workloads.run_pipeline`): attach
        the dispatch former and the per-query (raw, padded) lengths.

        Pre-compiles the closed bucketed shape set — power-of-two row
        counts x the bucket edges traffic actually uses — so no
        dispatch inside the serving loop ever pays (or measures) a
        first-shape XLA compile, and the executor's ``_warmed`` set
        stays bounded however many raw shapes the traffic offers.
        """
        self.former = former
        self._lengths = (None if lengths is None
                         else np.asarray(lengths, dtype=np.int64))
        self._padded = (None if padded is None
                        else np.asarray(padded, dtype=np.int64))
        if former is None:
            return
        self.max_batch = max(self.max_batch, int(former.max_batch))
        if self._padded is not None:
            edges = sorted({int(s) for s in self._padded})
        else:
            edges = sorted({int(t.shape[-1]) for t in self.queries})
        max_rows = max((int(t.shape[0]) for t in self.queries), default=1)
        self.engine.executor.warm_buckets(edges, self.max_batch * max_rows)

    def _dispatch_tokens(self, q: int) -> jnp.ndarray:
        """Query ``q``'s tokens, zero-padded along the sequence axis to
        its bucket edge so every dispatch shape comes from the closed
        warm set."""
        t = self.queries[q]
        if self._padded is None:
            return t
        seq = int(self._padded[q])
        raw = int(t.shape[-1])
        if seq == raw:
            return t
        return jnp.pad(t, ((0, 0), (0, seq - raw)))

    def begin_dispatch(self, q0: int,
                       step: RuntimeStep) -> "_LiveDispatchBuilder":
        return _LiveDispatchBuilder(self, q0, step)

    def _measure(self, config, first_measurement: bool):
        """Post-execution bookkeeping shared by both paths: bottleneck
        time, EMA estimate refresh, first-measurement detector arming."""
        eng = self.engine

        def finish(stage_times_per_query: np.ndarray) -> float:
            live = [i for i, c in enumerate(config) if c > 0]
            tmax = float(stage_times_per_query[live].max())
            eng._update_block_estimates(config, stage_times_per_query,
                                        self._slow)
            if first_measurement:
                # Arm detection against this query's measured
                # conditions, so interference beginning at the very
                # next query is a shift from this baseline rather
                # than the baseline.
                eng.runtime.arm(
                    eng._measured_source(self._slow, self._cf))
            return tmax

        return finish

    def _mesh_model(self, stage_times: np.ndarray, config,
                    assignment) -> tuple:
        """Scheduler-side sharded-stage model over *measured* per-stage
        compute times: (modeled bottleneck time, collective share).
        Wall-clock service latencies are never rewritten — only the
        capability/throughput signal the admission ledger and the trace
        columns consume (docs/SHARDING.md)."""
        eng = self.engine
        mt = mesh_stage_times(stage_times, config, assignment, eng.mesh,
                              self._cf, layer_costs=eng._coll_times)
        live = [i for i, c in enumerate(config) if c > 0]
        tmax = float(np.asarray(mt)[live].max())
        cf = _mesh_collective_frac(stage_times, config, assignment,
                                   eng.mesh, self._cf,
                                   layer_costs=eng._coll_times)
        return max(tmax, 1e-12), cf

    def execute(self, q: int, step: RuntimeStep) -> QueryRecord:
        eng = self.engine
        if self.former is not None:
            tokens = self._dispatch_tokens(q)
            rows = int(tokens.shape[0])
            pr = next_pow2(rows)
            eng.executor.ensure_warm(pr, int(tokens.shape[-1]))
            if pr > rows:
                tokens = jnp.concatenate(
                    [tokens, jnp.zeros((pr - rows, tokens.shape[-1]),
                                       tokens.dtype)])
        else:
            tokens = self.queries[q]
        finish = self._measure(step.config, eng._block_times is None)
        with span("engine.query", query=int(q), trial=int(step.serial)):
            t0 = time.perf_counter()
            _, st = eng.executor.run_query(tokens, step.config,
                                           slowdowns=self._slow)
            latency = time.perf_counter() - t0
        tmax = finish(st)
        coll_frac = 0.0
        if eng.mesh is not None and step.mesh is not None:
            tmax, coll_frac = self._mesh_model(st, step.config, step.mesh)
        if self.former is not None:
            # Batched dispatch is group-synchronous — a solo dispatch
            # holds the pipeline for its full drain, exactly like a
            # singleton formed batch.
            return QueryRecord(service_latency=latency,
                               throughput=1.0 / max(latency, 1e-12),
                               collective_frac=coll_frac)
        return QueryRecord(service_latency=latency,
                           throughput=1.0 / max(tmax, 1e-12),
                           collective_frac=coll_frac)

    def execute_many(self, q0: int, steps) -> BatchRecord:
        eng = self.engine
        n = len(steps)
        batch = [self.queries[q0 + i] for i in range(n)]
        # Never measure a first-shape XLA compile as service time.  The
        # key set here is bounded by construction (row sums never exceed
        # max_batch, one seq per chunk); the formed-dispatch paths use
        # the power-of-two warm family instead, since joins grow rows
        # dynamically.
        eng.executor.ensure_warm(sum(int(t.shape[0]) for t in batch),
                                 int(batch[0].shape[-1]))
        finish = self._measure(steps[0].config, eng._block_times is None)
        with span("engine.query", query=int(q0),
                  trial=int(steps[0].serial)):
            t0 = time.perf_counter()
            _, st = eng.executor.run_batch(batch, steps[0].config,
                                           slowdowns=self._slow)
            wall = time.perf_counter() - t0
        # Stage times cover the whole batch; the per-query estimate the
        # EMA consumes is the per-query share.
        tmax = max(finish(st / n), 1e-12)
        coll_fracs = None
        if eng.mesh is not None and steps[0].mesh is not None:
            tmax, cf = self._mesh_model(st / n, steps[0].config,
                                        steps[0].mesh)
            coll_fracs = np.broadcast_to(cf, n)
        # The batch holds the admission head for one batch-bottleneck
        # beat (per-query occupancy = tmax_batch / n) and every member
        # completes when the batch drains.  The run loop staggers member
        # starts by exactly that occupancy (members are queued by
        # construction), so attributing service = wall - i * occupancy
        # lands every completion at dispatch + wall — the stagger is
        # head-of-line accounting, not extra service.
        return BatchRecord(
            service_latencies=wall - np.arange(n) * tmax,
            throughputs=np.broadcast_to(1.0 / tmax, n),
            collective_fracs=coll_fracs)


class _LiveDispatchBuilder:
    """One physical batched dispatch, executed stage by stage.

    The live counterpart of the simulator's dispatch builder: formation
    members are stacked (sequence-padded to the bucket edge, rows
    rounded up to a warm power of two) and embedded once, then the run
    loop drives the pipeline one stage at a time through the executor's
    stage-granular ``run_stages``.  At each stage boundary a newly
    arrived same-bucket query can :meth:`join`: it pays one fused
    catch-up launch (embed + stages ``[0, s)`` over the joiner alone),
    then its rows are spliced into the in-flight batch, which resumes
    wider — no drain, no recompile (stage bounds and the batch dimension
    are runtime arguments).

    All times are wall-clock offsets from the dispatch launch.  Batched
    dispatch is group-synchronous — the next dispatch launches only
    after this one drains — so the record's throughput is ``1 / drain``.
    Every compiled shape this builder touches comes from the closed
    bucketed warm set (``configure_batching`` pre-compiled it); the
    ``ensure_warm`` calls before each timed window are bounded-set
    lookups, never compiles.
    """

    def __init__(self, live: "_LiveQueryExecutor", q0: int,
                 step: RuntimeStep):
        self._live = live
        eng = live.engine
        self._ex = eng.executor
        self._config = list(step.config)
        self._mesh = (list(step.mesh) if step.mesh is not None else None)
        self._S = len(self._config)
        self._bounds = None
        self._slow = live._slow
        self._first = eng._block_times is None
        self._trial = int(step.serial)
        self._span = contextlib.ExitStack()     # launch to drain
        self._seq = live._width(q0)
        self._members: List[int] = []
        self._starts: List[float] = []
        self._stage = 0
        self._launched = False
        self._t0 = 0.0
        self._x = None
        self._positions = None
        self._rows = 0       # real (non-padding) rows in self._x
        self._stage_times = np.zeros(self._S)
        self._stage_members = np.zeros(self._S)
        self._actual_tok = 0.0

    def add(self, q: int) -> None:
        """Formation member: present from stage 0 (start offset 0)."""
        self._members.append(q)
        self._starts.append(0.0)
        self._count_tokens(q)

    def _count_tokens(self, q: int) -> None:
        live = self._live
        rows = int(live.queries[q].shape[0])
        raw = (int(live._lengths[q]) if live._lengths is not None
               else int(live.queries[q].shape[-1]))
        self._actual_tok += float(rows) * float(raw)

    def _pad_rows(self, arr: jnp.ndarray, rows: int) -> jnp.ndarray:
        pr = next_pow2(rows)
        if pr > rows:
            arr = jnp.concatenate(
                [arr, jnp.zeros((pr - rows,) + arr.shape[1:], arr.dtype)])
        return arr

    def _launch(self) -> None:
        toks = [self._live._dispatch_tokens(q) for q in self._members]
        tokens = toks[0] if len(toks) == 1 else jnp.concatenate(toks)
        rows = int(tokens.shape[0])
        self._ex.ensure_warm(next_pow2(rows), self._seq)
        tokens = self._pad_rows(tokens, rows)
        self._rows = rows
        self._launched = True
        self._span.enter_context(span("engine.query", query=self._members[0],
                                      trial=self._trial))
        self._bounds = self._ex._device_bounds(self._config)
        self._t0 = time.perf_counter()
        self._x, self._positions = self._ex.embed_tokens(tokens)

    def _run_stage(self) -> None:
        s = self._stage
        self._stage_members[s] = len(self._members)
        self._x, st = self._ex.run_stages(
            self._x, self._positions, self._config, s, s + 1,
            slowdowns=self._slow, bounds=self._bounds)
        self._stage_times[s] = float(st[0])
        self._stage += 1

    def next_boundary(self) -> Optional[float]:
        """Run the next stage; return the boundary's wall-clock offset
        (a join opportunity) or ``None`` after the final stage."""
        if not self._launched:
            self._launch()
        self._run_stage()
        if self._stage >= self._S:
            return None
        return time.perf_counter() - self._t0

    def join(self, q: int) -> None:
        if not 0 < self._stage < self._S:
            raise QueryError("join() is only valid at a stage boundary")
        live, ex = self._live, self._ex
        tokens = live._dispatch_tokens(q)
        jrows = int(tokens.shape[0])
        new_rows = self._rows + jrows
        # Both shapes the timed window touches, checked warm up front.
        ex.ensure_warm(next_pow2(jrows), self._seq)
        ex.ensure_warm(next_pow2(new_rows), self._seq)
        tokens = self._pad_rows(tokens, jrows)
        self._starts.append(time.perf_counter() - self._t0)
        # One fused catch-up launch: embed, then every block of stages
        # [0, s) in a single ``stage_fn`` dispatch — block bounds are
        # runtime arguments, so the catch-up pays one dispatch + one
        # device sync however many stages the batch already ran (the
        # per-stage loop would price a join like a near-full solo
        # query).  Then splice the joiner's real rows into the
        # in-flight batch and re-pad to the next warm row count.
        h, positions = ex.embed_tokens(tokens)
        s = self._stage
        with span("executor.stage", stage=s - 1,
                  blocks=int(sum(self._config[:s])), syncs=2):
            t1 = time.perf_counter()
            h = ex._stage_fn(ex.params, h, positions,
                             self._bounds[0][0], self._bounds[s - 1][1])
            h.block_until_ready()
            fused = time.perf_counter() - t1
            x = jnp.concatenate([self._x[:self._rows], h[:jrows]])
            x = self._pad_rows(x, new_rows)
            x.block_until_ready()
        if self._slow is not None:
            # Interference emulation for the fused span: stretch by the
            # mean slowdown of the stages it covers (run_stages does
            # this per stage; the fused launch can't attribute within).
            stretch = float(np.mean(np.asarray(self._slow, float)[:s]))
            if stretch > 1.0:
                with span("executor.interference", stage=s - 1,
                          factor_pct=int(round(100 * stretch))):
                    time.sleep(fused * (stretch - 1.0))
        self._x = x
        self._positions = jnp.broadcast_to(
            jnp.arange(self._seq, dtype=jnp.int32),
            (int(x.shape[0]), self._seq))
        self._rows = new_rows
        self._members.append(q)
        self._count_tokens(q)

    def finish(self) -> DispatchRecord:
        if not self._launched:
            self._launch()
        while self._stage < self._S:
            self._run_stage()
        wait_ready(self._ex.head(self._x), "executor.head")
        drain = time.perf_counter() - self._t0
        self._span.close()
        # Per-query stage-time attribution for the EMA: each stage's
        # measured time is shared by the members present when it ran
        # (joiners' catch-up work is dispatch latency, not a per-block
        # time signal).
        done = self._live._measure(self._config, self._first)
        per_query = self._stage_times / np.maximum(self._stage_members, 1.0)
        done(per_query)
        coll_frac = 0.0
        if self._live.engine.mesh is not None and self._mesh is not None:
            _, coll_frac = self._live._mesh_model(per_query, self._config,
                                                  self._mesh)
        return DispatchRecord(
            start_offsets=np.asarray(self._starts, float),
            drain=drain,
            throughput=1.0 / max(drain, 1e-12),
            padded_tokens=float(next_pow2(self._rows)) * float(self._seq),
            actual_tokens=self._actual_tok,
            collective_frac=coll_frac)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params: Dict, num_eps: int,
                 scheduler: Union[str, SchedulerPolicy] = "odin",
                 alpha: int = DEFAULT_ALPHA,
                 rel_threshold: Optional[float] = None,
                 estimate_beta: float = 0.5,
                 executor: Optional[LocalPipelineExecutor] = None,
                 mesh=None,
                 coll_factor_schedule=None):
        self.cfg = cfg
        # Mesh-sliced stages (docs/SHARDING.md): scheduler-side modeling
        # over measured compute times.  ``mesh`` accepts anything
        # :func:`repro.core.mesh.resolve_mesh` takes (the RunSpec path
        # is the intended entry — docs/API.md); ``coll_factor_schedule
        # (q) -> float`` emulates collective contention the way
        # ``slowdown_schedule`` emulates compute interference.  Unset
        # (the default), every mesh code path is dormant and serving is
        # bit-identical to a pre-mesh build.
        self.mesh = resolve_mesh(mesh)
        self.coll_factor_schedule = coll_factor_schedule
        self._coll_times = (self.mesh.layer_costs(cfg.num_blocks)
                            if self.mesh is not None else None)
        self._initial_assignment = (
            balanced_assignment(self.mesh.devices, num_eps)
            if self.mesh is not None else None)
        # ``executor`` lets N engines share one jitted pipeline (the
        # multi-replica cluster pattern: replicas serve the same model,
        # so one compile + warmup serves the fleet, while every engine
        # keeps its own runtime/detector/estimate state).
        self.executor = (executor if executor is not None
                         else LocalPipelineExecutor(cfg, params))
        self.num_eps = num_eps
        # Weight of the newest measurement in the per-block clean-time
        # EMA.  0.5 (default) tracks fast; smaller values smooth
        # measurement jitter out of the estimates the explorer compares,
        # making exploration walks reproducible on noisy hosts.
        self.estimate_beta = float(estimate_beta)
        if isinstance(scheduler, str):
            self.policy = make_scheduler(scheduler, alpha=alpha,
                                         rel_threshold=rel_threshold,
                                         detector=MEASURED_DETECTOR_MODE)
            self.scheduler = scheduler
        else:
            self.policy = scheduler
            self.scheduler = getattr(scheduler, "name",
                                     type(scheduler).__name__)
        self._initial_config = balanced_config(cfg.num_blocks, num_eps)
        self.runtime = RebalanceRuntime(self.policy, self._initial_config,
                                        mesh=self._initial_assignment)
        # EMA of measured per-block times feeds the scheduler's trial
        # evaluations between real executions.
        self._block_times: Optional[np.ndarray] = None

    def _coll_factor_at(self, q: int) -> float:
        """Collective-contention factor for query ``q`` (1.0 quiet /
        unsharded)."""
        if self.mesh is None or self.coll_factor_schedule is None:
            return 1.0
        return float(self.coll_factor_schedule(q))

    def _measured_source(self, slowdowns,
                         coll_factor: float = 1.0) -> MeasuredTimeSource:
        """The scheduler's time source over the current block-time
        estimates — mesh-aware when a mesh is armed (the runtime syncs
        the committed assignment on every poll)."""
        if self.mesh is None:
            return MeasuredTimeSource(self._block_times, slowdowns)
        return MeasuredTimeSource(self._block_times, slowdowns,
                                  mesh=self.mesh,
                                  coll_times=self._coll_times,
                                  assignment=self.runtime.mesh,
                                  coll_factor=coll_factor)

    @property
    def config(self) -> List[int]:
        """Current committed stage configuration."""
        return list(self.runtime.config)

    def reset_policy(self) -> None:
        """Fresh serving window: abandon any in-flight phase, re-arm
        detection, and restart from the balanced initial configuration.
        Online block-time estimates are kept (they describe the model,
        not the window) — combined with ``estimate_beta = 0`` this makes
        scheduling decisions reproducible across serving windows, e.g.
        for A/B comparisons of ``serve(..., max_batch=...)``."""
        self.runtime.reset(self._initial_config,
                           mesh=self._initial_assignment)

    def estimated_peak_throughput(self) -> float:
        """Interference-free throughput of the starting configuration,
        from the online clean per-block estimates — the live analogue of
        the simulator's "executing alone" peak reference.  NaN until a
        query has been measured."""
        if self._block_times is None:
            return float("nan")
        clean = MeasuredTimeSource(self._block_times,
                                   np.ones(self.num_eps),
                                   mesh=self.mesh,
                                   coll_times=self._coll_times,
                                   assignment=self._initial_assignment)
        return throughput(clean.stage_times(self._initial_config))

    def _update_block_estimates(self, config: Sequence[int],
                                stage_times: np.ndarray,
                                slowdowns: Sequence[float]) -> None:
        """Refresh per-block clean-time estimates from a measured query.

        Vectorized: one ``np.repeat`` spreads each stage's de-slowed
        per-block time over its blocks (empty stages repeat zero times
        and contribute nothing), one fused EMA update runs in place.
        The first measurement seeds the estimates directly — averaging
        against a placeholder would hand the detector a reference that
        drifts for the next ~1/beta queries.
        """
        counts = np.asarray(config, dtype=np.int64)
        per_stage = (np.asarray(stage_times, float)
                     / np.maximum(np.asarray(slowdowns, float), 1e-9)
                     / np.maximum(counts, 1))
        per_block = np.repeat(per_stage, counts)
        if self._block_times is None:
            self._block_times = per_block.copy()
            return
        b = self.estimate_beta
        self._block_times[:] = (1.0 - b) * self._block_times + b * per_block

    def query_executor(self, queries: Sequence[jnp.ndarray],
                       slowdown_schedule,
                       max_batch: int = 1) -> "_LiveQueryExecutor":
        """This engine's :class:`~repro.workloads.QueryExecutor` half,
        for external drivers (``repro.cluster`` builds one per replica
        and feeds it through the shared run loop).  ``queries`` may be
        a *growing* sequence: the cluster appends each routed query
        before it executes."""
        return _LiveQueryExecutor(self, queries, slowdown_schedule,
                                  max_batch=max_batch)

    def _serve_impl(self, queries: Sequence[jnp.ndarray],
              slowdown_schedule,
              workload: Union[str, Workload, None] = "closed",
              workload_kwargs: Optional[dict] = None,
              max_batch: int = 1,
              batching: Union[str, object, None] = None,
              buckets: Union[str, object, None] = None,
              explore_in_batch: bool = False,
              admission: Union[str, object, None] = None,
              admission_kwargs: Optional[dict] = None,
              trace_mode: str = "dense",
              metrics_sink=None,
              sink_interval: Optional[int] = None,
              faults=None,
              retries=None,
              tiers=None,
              tiers_kwargs: Optional[dict] = None) -> PipelineTrace:
        """Serve ``queries`` under ``slowdown_schedule(q) -> per-EP
        slowdown factors (>= 1.0)``.

        ``workload`` picks the arrival process (``repro.workloads``):
        the default closed loop executes back-to-back exactly as before;
        open-loop workloads (rates in queries/second of wall-clock
        service time) additionally report queueing delay and offered
        vs. achieved load in the returned trace.

        ``max_batch > 1`` turns on batched serving (docs/WORKLOADS.md
        "Batching & the fast path"): queued arrivals are stacked and
        executed together, up to ``max_batch`` per dispatch, so bursts
        amortize stage dispatch + sync overhead instead of queueing
        one-by-one.  Batches never span an interference edge or a
        rebalance, and only queries that have already arrived join
        (a closed loop therefore still serves one at a time).

        ``batching`` selects the formed-dispatch path instead
        (docs/WORKLOADS.md "Continuous batching & length buckets"):
        ``"drain"`` forms length-bucketed batches that run to
        completion; ``"continuous"`` additionally admits arrivals into
        the in-flight batch at pipeline-stage boundaries via the
        executor's stage-granular ``run_stages`` — a joiner pays one
        fused catch-up launch instead of waiting out the full
        group-synchronous drain.  ``buckets`` picks the length buckets
        (``"pow2:lo:hi"``, an edge list, or ``None`` for raw lengths);
        queries are sequence-padded to their bucket edge and batches
        row-padded to powers of two, so every dispatch shape comes from
        a small pre-compiled set.  ``explore_in_batch`` lets an
        exploration trial ride at the head of a formed batch instead of
        forcing serial one-at-a-time processing.  With ``batching``
        set, ``max_batch`` caps the formed dispatch width.

        ``admission`` selects a :mod:`repro.control` admission policy
        (e.g. ``admission="slo_shed", admission_kwargs={"slo":
        0.25}`` — SLO in wall-clock seconds); shed queries are turned
        away before touching the executor and reported through the
        trace's shed/goodput surface (docs/CONTROL.md).

        ``trace_mode="streaming"`` / ``metrics_sink`` select the
        flat-memory telemetry path (docs/TELEMETRY.md), identically to
        the simulator: streaming runs return a
        :class:`~repro.telemetry.StreamingTrace`, sinks receive
        periodic snapshots in either mode.

        ``faults`` / ``retries`` inject deterministic failures and arm
        the retry budget (docs/FAULTS.md) — the same surface as the
        simulator, realized by wrapping this engine's executor in a
        :class:`~repro.faults.FaultingExecutor`.  Both default off
        (fault-free serving is unchanged).

        ``tiers`` / ``tiers_kwargs`` stamp arrivals with QoS tiers
        (docs/QOS.md) exactly as in the simulator — the resolution and
        the per-arrival draws live in the shared run loop, so a sim
        and a live run of the same seed see identical tier plans.
        """
        seq_max = max((int(t.shape[-1]) for t in queries), default=1)
        former = resolve_batching(batching, max_batch=max_batch,
                                  buckets=buckets,
                                  explore_in_batch=explore_in_batch,
                                  seq=seq_max)
        lengths = None
        if former is not None:
            # Real query shapes are the length distribution here — the
            # generators in repro.workloads.lengths drive query
            # *construction* (launch CLI, examples), not serving.
            lengths = np.array([int(t.shape[-1]) for t in queries],
                               dtype=np.int64)
        live = self.query_executor(
            queries, slowdown_schedule,
            max_batch=(former.max_batch if former is not None
                       else max_batch))
        trace = _run_pipeline_impl(live, self.runtime, len(queries),
                             workload=workload,
                             workload_kwargs=workload_kwargs,
                             scheduler_name=self.scheduler,
                             admission=admission,
                             admission_kwargs=admission_kwargs,
                             trace_mode=trace_mode,
                             metrics_sink=metrics_sink,
                             sink_interval=sink_interval,
                             former=former,
                             lengths=lengths,
                             faults=faults, retries=retries,
                             tiers=tiers, tiers_kwargs=tiers_kwargs)
        # The peak reference only exists after measurement: stamp it
        # post-hoc so the trace's SLO metrics work like the simulator's.
        trace.peak_throughput = self.estimated_peak_throughput()
        return trace

    def serve(self, queries: Sequence[jnp.ndarray],
              slowdown_schedule,
              workload: Union[str, Workload, None] = "closed",
              workload_kwargs: Optional[dict] = None,
              max_batch: int = 1,
              batching: Union[str, object, None] = None,
              buckets: Union[str, object, None] = None,
              explore_in_batch: bool = False,
              admission: Union[str, object, None] = None,
              admission_kwargs: Optional[dict] = None,
              trace_mode: str = "dense",
              metrics_sink=None,
              sink_interval: Optional[int] = None,
              faults=None,
              retries=None,
              tiers=None,
              tiers_kwargs: Optional[dict] = None) -> PipelineTrace:
        """Serve ``queries`` under ``slowdown_schedule(q) -> per-EP
        slowdown factors``.

        Thin wrapper over the unified :class:`repro.api.RunSpec` path
        (one declaration, one dispatcher — docs/API.md); the kwargs
        here map 1:1 onto spec fields and new options land on the spec
        (or, for physical per-engine state like the device mesh, on
        the :class:`ServingEngine` constructor — docs/SHARDING.md)
        instead of this signature.  See :meth:`_serve_impl` for the
        full kwarg-level documentation.
        """
        from repro import api
        spec = api.RunSpec(
            engine=self, queries=queries, schedule=slowdown_schedule,
            workload=api.WorkloadSpec(name=workload,
                                      kwargs=workload_kwargs),
            admission=api.AdmissionSpec(name=admission,
                                        kwargs=admission_kwargs),
            batching=api.BatchingSpec(mode=batching, max_batch=max_batch,
                                      buckets=buckets,
                                      explore_in_batch=explore_in_batch),
            faults=api.FaultsSpec(plan=faults),
            retries=api.RetriesSpec(policy=retries),
            tiers=api.TiersSpec(spec=tiers, kwargs=tiers_kwargs),
            telemetry=api.TelemetrySpec(trace_mode=trace_mode,
                                        metrics_sink=metrics_sink,
                                        sink_interval=sink_interval))
        return api.run(spec)
