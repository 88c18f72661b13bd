"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload qwen3-4b.interfere --seed 7 \
        --seconds 20 --trace 0

A cell names a configuration (``bench/configs/<config>.json``, with its
plain f32 reference in ``bench/reference/``), a traffic mix
(``bench/traffic/<traffic>.json``) and the limits of its check
(``bench/limits/<cell>.json``); its per-layer metrics are readers in
``bench/metrics/<metric>.py``.  All are found by name, so a new cell,
mix or metric is new files and new entries, never an edit here.

One run:

1. names the device; without a TPU, or with fewer chips than the cell
   asks for, it exits non-zero and prints no result;
2. set-up, timed from the moment JAX has the chip: the program's
   imports, weights on the device from ``--seed`` in one jitted call,
   the engine with the cell's one prompt shape compiled, a few warm-up
   queries through ``repro.api.run``; JAX's compilation cache sits at
   ``<checkout>/.jax_cache``;
3. the window: one client in a closed loop sends the seeded prompts
   through ``repro.api.run`` in successive calls on the same engine
   until ``--seconds`` have passed; an executor of the benchmark's own
   stamps each query's completion on the host clock;
4. the check: once the window has closed, the logits of a seeded
   sample of the queries it served are compared with the f32
   reference (``correct``);
5. one JSON line: with ``--trace 0`` the end-to-end metrics, with
   ``--trace 1`` the per-layer ones, read from a profiler trace of the
   window and from the stamps.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional, Tuple  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = BENCH / ".trace"
#: Host spans the benchmark records around calls into the program,
#: innermost first.
SPANS = ("run_query", "api.run")
#: Reported in place of a gap that is not finite.
F32_MAX = 3.4028234663852886e38


def fail(msg: str, code: int = 1) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


# -- the cell, by name ---------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(entry: dict, cell: str) -> bool:
    return cell in entry.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json")
            .read_text()),
        limits=json.loads(
            (root / "bench" / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def reference_module(config: dict):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    return importlib.import_module(f"reference.{config['reference']}")


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_of(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# -- traffic ---------------------------------------------------------------


class Interference:
    """Per-EP slowdowns on the wall clock from the window's start:
    ``on_s`` seconds on, ``off_s`` off, first onset at
    ``first_onset_s``; the k-th on-period slows EP ``eps[k % len]`` by
    ``factor``.  The program realises it through the ``schedule`` of
    ``RunSpec``, which it asks before each query."""

    def __init__(self, spec: Optional[dict], num_eps: int):
        self.spec = spec
        self.num_eps = num_eps
        self.t0 = 0.0

    def period(self, t: float) -> Optional[int]:
        """Index of the on-period at ``t`` seconds into the window."""
        s = self.spec
        if s is None or t < s["first_onset_s"]:
            return None
        k, r = divmod(t - s["first_onset_s"], s["on_s"] + s["off_s"])
        return int(k) if r < s["on_s"] else None

    def __call__(self, q: int) -> List[float]:
        slow = [1.0] * self.num_eps
        k = self.period(time.perf_counter() - self.t0)
        if k is not None:
            slow[self.spec["eps"][k % len(self.spec["eps"])]] = \
                float(self.spec["factor"])
        return slow

    def onsets(self, end: float) -> List[Tuple[float, float, int]]:
        """``(on, off, ep)`` on the host clock, for every on-period that
        begins before ``end``."""
        s, out = self.spec, []
        if s is None:
            return out
        k = 0
        while True:
            on = self.t0 + s["first_onset_s"] + k * (s["on_s"] + s["off_s"])
            if on >= end:
                return out
            out.append((on, on + s["on_s"], s["eps"][k % len(s["eps"])]))
            k += 1


# -- the run -----------------------------------------------------------------


@dataclasses.dataclass
class Record:
    """One query as the client saw it: host-clock start and completion of
    ``run_query``, the stage split it ran under, its prompt and the
    tokens its logits put first (on the device until the check)."""
    t0: float
    t1: float
    config: Tuple[int, ...]
    tokens: object
    served: object
    serial: bool = False


@dataclasses.dataclass
class Run:
    """What the per-layer readers see."""
    cell: Cell
    seq: int
    records: List[Record]
    window: Tuple[float, float]         # host clock: start, last completion
    latencies: List[float]              # seconds, one per query
    onsets: List[Tuple[float, float, int]]
    costs: dict
    peak: dict
    balanced: Tuple[int, ...]
    profile: object = None              # trace.Profile with --trace 1


def executor_class(base):
    """``base`` (the program's ``LocalPipelineExecutor``) with the
    benchmark's stamps and spans around ``run_query``."""
    import jax
    import jax.numpy as jnp

    class StampedExecutor(base):
        def __init__(self, cfg, params, annotate: bool):
            super().__init__(cfg, params)
            self.records: List[Record] = []
            self._annotate = annotate
            self._served = jax.jit(
                lambda logits: jnp.argmax(logits[0], axis=-1)
                .astype(jnp.int32))

        def run_query(self, tokens, config, slowdowns=None):
            span = (jax.profiler.TraceAnnotation("run_query")
                    if self._annotate else contextlib.nullcontext())
            with span:
                t0 = time.perf_counter()
                logits, times = super().run_query(tokens, config, slowdowns)
                t1 = time.perf_counter()
            self.records.append(Record(t0, t1, tuple(int(c) for c in config),
                                       tokens, self._served(logits)))
            return logits, times

    return StampedExecutor


def percentile(xs, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, executor_base=None,
             program_config=None) -> dict:
    """One run; returns the result line's object (``check`` last).

    The keywords are for tests on the CPU: ``require_tpu=False`` skips
    the look for a chip, ``executor_base`` puts another executor under
    the stamps, ``program_config`` serves another ``ModelConfig`` than
    the configuration's ``arch``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    devices = jax.devices()
    # Set-up is timed from here: process start and the TPU runtime's
    # start (8-14 s on one v5e host, varying by a quarter between runs
    # of the same code) belong to neither side of a comparison.
    t_ready = time.time()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"bench: device {device}", file=sys.stderr, flush=True)
    if require_tpu and dev.platform != "tpu":
        fail(f"no TPU: JAX found {device}")
    if len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, JAX found "
             f"{len(devices)}")
    peak = peak_of(dev.device_kind) if require_tpu else {}

    from repro import api
    from repro.configs import get_config
    from repro.core.pipeline_state import balanced_config
    from repro.launch.serve import build_engine
    from repro.pipeline.executor import LocalPipelineExecutor

    c, tr = cell.config, cell.traffic
    ref = reference_module(c)
    cfg = program_config or get_config(c["arch"])
    for key, want in c["program"].items():
        got = cfg
        for part in key.split("."):
            got = getattr(got, part)
        if got != want:
            raise ValueError(f"{c['name']}: the program's {key} is {got}, "
                             f"the configuration file says {want}")
    dtype = jnp.dtype(c["dtype"])
    dep = c["deployment"]
    seq = int(tr["prompt_tokens"])

    # Set-up: weights from the seed, prompts, engine, warm-up queries.
    from reference.common import weight_key
    stamps = [("program imports and config", time.time())]
    weights = ref.init_weights(c, weight_key(seed), dtype)
    jax.block_until_ready(weights)
    stamps.append(("weights", time.time()))
    rng = np.random.default_rng(seed)
    pool = rng.integers(0, c["vocab_size"], (int(tr["prompt_pool"]), 1, seq))
    prompts = jax.device_put(list(pool.astype(np.int32)))
    Executor = executor_class(executor_base or LocalPipelineExecutor)
    executor = Executor(cfg, weights, annotate=trace)
    eng = build_engine(cfg, weights, [seq], num_eps=dep["num_eps"],
                       scheduler=dep["scheduler"], alpha=dep["alpha"],
                       executor=executor)
    stamps.append(("engine", time.time()))
    steady = Interference(None, dep["num_eps"])
    n_warm = int(tr["warmup_queries"])
    api.run(api.RunSpec(engine=eng, queries=prompts[-n_warm:],
                        schedule=steady))
    jax.block_until_ready([r.served for r in executor.records])
    executor.records.clear()
    stamps.append(("warm-up", time.time()))

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    sched = Interference(tr.get("interference"), dep["num_eps"])
    per_call = int(tr["queries_per_call"])
    serial, i = [], 0
    setup_s = time.time() - t_ready
    t_start = time.perf_counter()
    sched.t0 = t_start
    while time.perf_counter() - t_start < seconds:
        chunk = [prompts[(i + j) % len(prompts)] for j in range(per_call)]
        span = (jax.profiler.TraceAnnotation("api.run") if trace
                else contextlib.nullcontext())
        with span:
            out = api.run(api.RunSpec(engine=eng, queries=chunk,
                                      schedule=sched))
        serial.extend(bool(s) for s in out.serial_mask)
        i += per_call
    records = executor.records
    jax.block_until_ready([r.served for r in records])
    if trace:
        jax.profiler.stop_trace()
    if len(serial) != len(records):
        raise RuntimeError(f"{len(records)} queries ran, the program's "
                           f"traces list {len(serial)}")
    for r, s in zip(records, serial):
        r.serial = s
    t_end = records[-1].t1
    latencies = np.diff([t_start] + [r.t1 for r in records])
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices[:cell.chips])

    balanced = tuple(balanced_config(cfg.num_blocks, dep["num_eps"]))
    run = Run(cell=cell, seq=seq, records=records, window=(t_start, t_end),
              latencies=list(latencies), onsets=sched.onsets(t_end),
              costs=ref.costs(c, seq, dtype), peak=peak, balanced=balanced)
    del eng, executor
    gc.collect()

    check, notes = compare(run, ref, weights, seed, cell.limits)
    correct = all(v["value"] <= v["limit"] for v in check.values())

    result = {"correct": bool(correct), "attempted": len(records),
              "failed": 0}
    window_s = t_end - t_start
    if not trace:
        values = {
            "tokens_per_s": seq * len(records) / window_s,
            "latency_p50_ms": percentile(latencies, 50) * 1e3,
            "latency_p95_ms": percentile(latencies, 95) * 1e3,
            "setup_s": setup_s,
        }
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        from devtrace import find_xplane, idle_by_span, load
        run.profile = load(find_xplane(str(TRACE_DIR)), SPANS)
        result["metrics"] = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        a, b = run.profile.window("api.run")
        device["busy_s"] = run.profile.busy_ns(a, b) * 1e-9
        device["window_s"] = (b - a) * 1e-9
        top = sorted(run.profile.op_ns.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(idle_by_span(run.profile, a, b, SPANS).items(),
                      key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": [[k, v * 1e-9] for k, v in idle]}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    result["device"] = device
    prev = t_ready
    phases = [f"process start to chip ready {t_ready - T_START:.3f}"]
    for name, t in stamps:
        phases.append(f"{name} {t - prev:.3f}")
        prev = t
    notes = [f"set-up {setup_s:.3f} s; " + ", ".join(phases),
             timeline(run)] + notes
    for line in notes:
        print(f"bench: {line}", file=sys.stderr)
    for k, v in check.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    result["check"] = check
    return result


def timeline(run: Run) -> str:
    """One line of the window, for the reader of a run's log: queries
    completed and their mean client latency in each on- and off-period
    (each 5 s without interference), trials, and the splits committed."""
    import numpy as np
    t0, t1 = run.window
    edges = sorted({t0, t1, *[t for on, off, _ in run.onsets
                              for t in (on, off) if t0 < t < t1]})
    if len(edges) == 2:
        edges = list(np.arange(t0, t1, 5.0)) + [t1]
    done = np.array([r.t1 for r in run.records])
    lat = np.asarray(run.latencies)
    parts = []
    for a, b in zip(edges, edges[1:]):
        m = (done > a) & (done <= b)
        if m.any():
            parts.append(f"{a - t0:.1f}-{b - t0:.1f}s {int(m.sum())}q "
                         f"{1e3 * lat[m].mean():.2f}ms")
    commits, last = [], run.balanced
    for r in run.records:
        if not r.serial and r.config != last:
            commits.append(f"{r.t0 - t0:.2f}s {list(r.config)}")
            last = r.config
    trials = sum(r.serial for r in run.records)
    return (f"window: {'; '.join(parts)}; {trials} trials; commits: "
            f"{', '.join(commits) or 'none'}")


def sample(run: Run, seed: int, k: int) -> List[int]:
    """Indices of the queries the check compares, drawn from the seed:
    ``k`` of all served, plus the first query served under each split
    other than the balanced one that the rebalancer committed, up to
    ``k`` of those (trial queries are included among the random)."""
    import numpy as np
    rng = np.random.default_rng([int(seed) % 2**63, 12])
    n = len(run.records)
    pick = list(rng.choice(n, size=min(k, n), replace=False))
    seen = {run.balanced}
    for i, r in enumerate(run.records):
        if len(seen) > k:
            break
        if not r.serial and r.config not in seen:
            seen.add(r.config)
            pick.append(i)
    return sorted(set(int(i) for i in pick))


def widest_gap(ref_logits, served):
    """The widest gap, over positions, by which the reference's logit of
    the token the served logits put first lies below the reference's
    best logit; ``ref_logits [S, V]`` f32, ``served [S]`` token ids."""
    import jax.numpy as jnp
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(best - got)


def compare(run: Run, ref, weights, seed: int, limits: dict):
    """``widest_gap`` over every position of the sampled queries."""
    import jax

    forward = ref.make_forward(run.cell.config)
    gap = jax.jit(widest_gap)
    idx = sample(run, seed, int(run.cell.config["check_queries"]))
    worst, splits, notes = 0.0, set(), []
    for i in idx:
        r = run.records[i]
        logits = forward(weights, r.tokens[0])
        g = float(gap(logits, r.served))
        if not math.isfinite(g):
            notes.append(f"query {i}: the reference's logits are not finite")
            g = F32_MAX
        worst = max(worst, g)
        splits.add(r.config)
        del logits
    notes += [f"compared {len(idx)} queries, {len(idx) * run.seq} positions, "
             f"under splits {sorted(splits)}"]
    return {"logit_gap": {"value": worst,
                          "limit": float(limits["logit_gap"])}}, notes


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # Before JAX loads: the compilation cache at a fixed path inside the
    # checkout, whatever the environment names; TPU logs off (they would
    # go to /tmp).
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    cell = load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
