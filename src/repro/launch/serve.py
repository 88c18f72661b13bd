"""Serving driver: ODIN-managed inference pipeline under interference.

    python -m repro.launch.serve --arch qwen3-4b --scheduler odin \
        --eps 4 --queries 100 [--alpha 10]

Runs the reduced config of the chosen family through the recompile-free
pipeline executor on the host device, injects interference episodes, and
reports latency / throughput / rebalance statistics for ODIN vs LLS.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs import ARCH_IDS, get_smoke_config
from repro.control import available_admission_policies
from repro.core.database import paper_scenarios
from repro.models import Model
from repro.qos import available_tiers
from repro.schedulers import available_schedulers
from repro.serving import ServingEngine
from repro.workloads import available_workloads, make_lengths

#: Root of the checkout this module runs from (``src/repro/launch/``).
CHECKOUT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is set here.  Otherwise the cache goes to the git-ignored
    ``<checkout>/.jax_cache``: a fixed path, so the next process finds
    what this one compiled.  Entry points call this from ``main()``,
    never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_params(cfg, seed: int, dtype):
    """Random ``dtype`` weights for ``cfg`` from ``seed``, made in one
    jitted program: eager init materialises every tensor in f32 before
    the cast (3.6 GB for one Qwen3-4B weight stack)."""
    init = jax.jit(Model(cfg).init_params, static_argnums=1)
    return init(jax.random.PRNGKey(seed), dtype)


def slowdown_schedule(windows, num_eps: int):
    """``schedule(q) -> per-EP slowdown factors`` over interference
    windows ``(start, end, ep, factor)``: queries ``start <= q < end``
    see EP ``ep`` slowed by ``factor``."""
    def schedule(q):
        slow = [1.0] * num_eps
        for s, e, ep, f in windows:
            if s <= q < e:
                slow[ep] = f
        return slow
    return schedule


def build_engine(cfg, params, lengths=(), *, num_eps: int, scheduler: str,
                 alpha: int, executor=None) -> ServingEngine:
    """A :class:`ServingEngine` with each query length in ``lengths``
    compiled before serving, so no compile lands in a measured query."""
    eng = ServingEngine(cfg, params, num_eps=num_eps, scheduler=scheduler,
                        alpha=alpha, executor=executor)
    for length in sorted({int(x) for x in lengths}):
        eng.executor.ensure_warm(1, length)
    return eng


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-4b")
    # Every registered policy is servable except the oracle, which needs
    # a caller-supplied solver (the simulator wires one in).
    ap.add_argument("--scheduler", default="odin",
                    choices=tuple(n for n in available_schedulers()
                                  if n != "oracle"))
    ap.add_argument("--alpha", type=int, default=10)
    ap.add_argument("--eps", type=int, default=4)
    ap.add_argument("--queries", type=int, default=100)
    ap.add_argument("--blocks", type=int, default=0,
                    help="override the layer count, a multiple of the "
                         "layer pattern's period (0 = config default)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--freq", type=int, default=25,
                    help="interference frequency period (queries)")
    ap.add_argument("--duration", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workload", default="closed",
                    choices=tuple(n for n in available_workloads()
                                  if n != "trace"),
                    help="arrival process (docs/WORKLOADS.md); open-loop "
                         "runs report queueing delay separately")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="open-loop arrival rate, q/s (poisson rate / "
                         "bursty burst_rate; bursty idles between bursts)")
    ap.add_argument("--max-batch", type=int, default=1,
                    help="batched serving: stack up to N queued arrivals "
                         "per dispatch (docs/WORKLOADS.md; >1 only pays "
                         "off for open-loop workloads with bursts)")
    ap.add_argument("--batching", default="none",
                    choices=("none", "drain", "continuous"),
                    help="formed-dispatch mode (docs/WORKLOADS.md "
                         "'Continuous batching & length buckets'): drain "
                         "runs length-bucketed batches to completion, "
                         "continuous admits arrivals into the in-flight "
                         "batch at stage boundaries; --max-batch caps the "
                         "dispatch width")
    ap.add_argument("--buckets", default="",
                    help="length buckets for --batching: 'pow2:lo:hi', a "
                         "comma list like '64,128,256', or empty for a "
                         "single bucket at the longest query")
    ap.add_argument("--lengths", default="fixed",
                    choices=("fixed", "uniform", "bimodal"),
                    help="per-query sequence-length distribution "
                         "(repro.workloads.lengths; anchored at --seq: "
                         "uniform draws [seq/4, seq], bimodal mixes seq/4 "
                         "and seq)")
    ap.add_argument("--admission", default="none",
                    choices=tuple(available_admission_policies()),
                    help="admission policy (docs/CONTROL.md); slo_shed / "
                         "adaptive_batch need --slo")
    ap.add_argument("--slo", type=float, default=0.0,
                    help="latency objective in seconds for --admission "
                         "slo_shed / adaptive_batch (0 = unset)")
    ap.add_argument("--trace-mode", default="dense",
                    choices=("dense", "streaming"),
                    help="streaming folds per-query telemetry into "
                         "constant-memory sketches/rollups instead of "
                         "dense arrays (docs/TELEMETRY.md)")
    ap.add_argument("--metrics-export", default="", metavar="PATH",
                    help="write the final metrics registry to PATH after "
                         "the run (.prom/.txt Prometheus text exposition, "
                         "anything else JSON; needs --trace-mode "
                         "streaming; docs/TELEMETRY.md)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve a fleet of N engine replicas behind a "
                         "router (docs/CLUSTER.md); hedging and "
                         "health-aware routing need N >= 2")
    ap.add_argument("--router", default="round_robin",
                    help="fleet router registry name (docs/CLUSTER.md; "
                         "'edf' and 'downgrade' are tier-aware, "
                         "docs/QOS.md); needs --replicas >= 2 or "
                         "--configs")
    ap.add_argument("--tiers", default="", metavar="NAMES",
                    help="comma list of QoS tier presets, e.g. "
                         "'interactive,best_effort' (docs/QOS.md): "
                         "arrivals are stamped with tier/deadline/value "
                         "and the trace grows per-tier accounting")
    ap.add_argument("--configs", default="", metavar="ARCHS",
                    help="comma list of arch ids, one per replica — a "
                         "heterogeneous fleet (docs/QOS.md); replicas "
                         "whose arch differs from the first are labeled "
                         "pool 'small' (the --router downgrade targets); "
                         "overrides --replicas")
    ap.add_argument("--faults", default="", metavar="SPEC",
                    help="fault plan spec, e.g. 'crash@50+20:r=0,"
                         "flaky@0+1000:p=0.05' (docs/FAULTS.md); windows "
                         "are query-indexed on a single engine and "
                         "wall-clock (open-loop workloads only) on a "
                         "--replicas fleet")
    ap.add_argument("--retries", type=int, default=-1, metavar="N",
                    help="per-query retry budget with exponential "
                         "backoff (docs/FAULTS.md); -1 leaves the fault "
                         "machinery unarmed")
    ap.add_argument("--hedge-after", type=float, default=0.0,
                    metavar="SECONDS",
                    help="hedge a dispatch to a healthy peer when its "
                         "projected wait exceeds this (docs/FAULTS.md; "
                         "needs --replicas >= 2)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    configs_list = [c.strip() for c in args.configs.split(",")
                    if c.strip()]
    if configs_list:
        unknown = [c for c in configs_list if c not in ARCH_IDS]
        if unknown:
            ap.error(f"--configs has unknown arch ids {unknown}; "
                     f"pick from {ARCH_IDS}")
        if args.replicas > 1 and args.replicas != len(configs_list):
            ap.error(f"--configs names {len(configs_list)} replicas but "
                     f"--replicas says {args.replicas}")
        args.replicas = len(configs_list)
        args.arch = configs_list[0]
    if args.tiers:
        bad = [t.strip() for t in args.tiers.split(",")
               if t.strip() not in available_tiers()]
        if bad:
            ap.error(f"--tiers has unknown presets {bad}; pick from "
                     f"{available_tiers()}")

    configure_compile_cache()
    cfg = get_smoke_config(args.arch)
    if args.blocks:
        cfg = dataclasses.replace(cfg, num_layers=args.blocks)
    params = init_params(cfg, args.seed, jnp.float32)

    rng = np.random.default_rng(args.seed)
    if cfg.embedding_inputs:
        raise SystemExit("serve demo uses token models; pick a non-VLM arch")
    if args.lengths == "fixed":
        lens = np.full(args.queries, args.seq, dtype=np.int64)
    else:
        kw = (dict(lo=max(1, args.seq // 4), hi=args.seq)
              if args.lengths == "uniform"
              else dict(short=max(1, args.seq // 4), long=args.seq,
                        p_long=0.2))
        lens = make_lengths(args.lengths, seed=args.seed,
                            **kw).sample(args.queries)
    # Heterogeneous fleets share the query stream, so token ids must be
    # valid for every replica's model: draw below the smallest vocab.
    vocab = cfg.vocab_size
    if configs_list:
        vocab = min(get_smoke_config(c).vocab_size for c in configs_list)
    queries = [jnp.asarray(rng.integers(0, vocab, (1, int(L))))
               for L in lens]

    scens = paper_scenarios()
    events = []
    for start in range(args.freq, args.queries, args.freq):
        events.append((start, start + args.duration,
                       int(rng.integers(args.eps)),
                       float(scens[rng.integers(len(scens))].slowdown_mean)))

    schedule = slowdown_schedule(events, args.eps)
    # Bucketed serving pre-warms its own closed shape set
    # (configure_batching); the unbucketed path compiles each raw length
    # once, up front.
    eng = build_engine(cfg, params, lens if args.batching == "none" else (),
                       num_eps=args.eps, scheduler=args.scheduler,
                       alpha=args.alpha)
    if args.workload == "closed":
        wl_kwargs = None             # --rate is irrelevant (and may be 0)
    else:
        wl_kwargs = dict(rate=args.rate, burst_rate=args.rate,
                         base_rate=args.rate / 10,
                         mean_burst=5.0 / args.rate * args.eps,
                         mean_gap=10.0 / args.rate * args.eps,
                         seed=args.seed)
    if args.admission in ("slo_shed", "adaptive_batch") and args.slo <= 0:
        ap.error(f"--admission {args.admission} requires --slo > 0")
    if args.metrics_export and args.trace_mode != "streaming":
        ap.error("--metrics-export needs --trace-mode streaming (the "
                 "dense trace has no metrics registry)")
    adm_kwargs = {"slo": args.slo} if args.slo > 0 else None
    faults = args.faults or None
    retries = None if args.retries < 0 else args.retries
    hedge_after = args.hedge_after if args.hedge_after > 0 else None
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if hedge_after is not None and args.replicas < 2:
        ap.error("--hedge-after needs --replicas >= 2 (hedging "
                 "dispatches to a healthy peer)")
    if args.replicas > 1:
        # Fleet path: same-arch replicas share the jitted executor but
        # keep their own runtime/detector/estimates (docs/CLUSTER.md);
        # --configs replicas of a different arch get their own model,
        # executor and warmed-shape caches (docs/QOS.md).
        if args.batching != "none" or args.max_batch > 1:
            ap.error("--replicas > 1 serves per-query; drop --batching "
                     "/ --max-batch")
        if args.metrics_export:
            ap.error("--metrics-export is single-engine only (the "
                     "fleet trace has no one registry to export)")
        if faults is not None and args.workload == "closed":
            ap.error("fleet fault windows are wall-clock "
                     "(docs/FAULTS.md); pick an open-loop --workload")
        archs = configs_list or [args.arch] * args.replicas
        # First engine per arch owns that arch's jitted executor and
        # warmed shapes; same-arch replicas share it, distinct archs
        # compile their own.
        lead = {args.arch: (cfg, params, eng)}
        engines, pools = [], []
        for arch in archs:
            if arch not in lead:
                c2 = get_smoke_config(arch)
                if args.blocks:
                    c2 = dataclasses.replace(c2, num_layers=args.blocks)
                p2 = init_params(c2, args.seed, jnp.float32)
                e2 = build_engine(c2, p2, lens, num_eps=args.eps,
                                  scheduler=args.scheduler,
                                  alpha=args.alpha)
                lead[arch] = (c2, p2, e2)
            acfg, aparams, first = lead[arch]
            if not any(x is first for x in engines):
                e = first
            else:
                e = build_engine(acfg, aparams, num_eps=args.eps,
                                 scheduler=args.scheduler,
                                 alpha=args.alpha,
                                 executor=first.executor)
            engines.append(e)
            pools.append("default" if arch == archs[0] else "small")
        # The CLI drives the unified RunSpec path directly (docs/API.md)
        # — one declaration either way, and the spec's to_dict() is the
        # run's reproducible description.
        metrics = api.run(api.RunSpec(
            engines=engines, queries=queries, schedule=schedule,
            workload=api.WorkloadSpec(name=args.workload,
                                      kwargs=wl_kwargs),
            admission=api.AdmissionSpec(name=args.admission,
                                        kwargs=adm_kwargs),
            faults=api.FaultsSpec(plan=faults, hedge_after=hedge_after),
            retries=api.RetriesSpec(policy=retries),
            tiers=api.TiersSpec(spec=(args.tiers or None)),
            telemetry=api.TelemetrySpec(trace_mode=args.trace_mode),
            cluster=api.ClusterSpec(num_replicas=len(engines),
                                    router=args.router,
                                    pools=tuple(pools))))
        s = metrics.summary()
        s["final_config"] = None
    else:
        if args.router != "round_robin":
            ap.error("--router needs a fleet: pass --replicas >= 2 or "
                     "--configs")
        metrics = api.run(api.RunSpec(
            engine=eng, queries=queries, schedule=schedule,
            workload=api.WorkloadSpec(name=args.workload,
                                      kwargs=wl_kwargs),
            admission=api.AdmissionSpec(name=args.admission,
                                        kwargs=adm_kwargs),
            batching=api.BatchingSpec(
                mode=(None if args.batching == "none"
                      else args.batching),
                max_batch=args.max_batch,
                buckets=(args.buckets or None)),
            faults=api.FaultsSpec(plan=faults),
            retries=api.RetriesSpec(policy=retries),
            tiers=api.TiersSpec(spec=(args.tiers or None)),
            telemetry=api.TelemetrySpec(trace_mode=args.trace_mode)))
        s = metrics.summary()
        configs = metrics.configs
        s["final_config"] = configs[-1] if configs else None
    if args.metrics_export:
        from repro.telemetry import export_path_format, render_export
        path, fmt = export_path_format(args.metrics_export)
        with open(path, "w") as f:
            f.write(render_export(metrics.registry, fmt))
        if not args.json:
            print(f"metrics registry ({fmt}) -> {path}")
    if args.json:
        print(json.dumps(s))
    else:
        print(f"{cfg.name} scheduler={args.scheduler}")
        for k, v in s.items():
            print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
