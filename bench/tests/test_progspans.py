"""The readers of the program's own spans: against sums by hand on a
synthetic profile, on a small trace of the program recorded on a TPU
v5e (``data/program.xplane.pb``, made by ``record_program_trace.py``),
and in a whole traced run on the CPU at smoke widths."""
import shutil
import types
from pathlib import Path

import numpy as np
import pytest

import devtrace
import progspans
import run as bench
from test_faults import small_cell

DATA = Path(__file__).resolve().parent / "data"
PROGRAM = DATA / "program.xplane.pb"
READERS = ("executor.host_syncs", "executor.bounds_ms",
           "executor.launch_idle_ms", "rebalance.detections",
           "rebalance.explore_s", "runloop.compiles")
MS = 1_000_000          # ns per ms


def iv(*pairs):
    return np.asarray([(a * MS, b * MS) for a, b in pairs],
                      np.int64).reshape(-1, 2)


def synthetic():
    """Two queries of two stages, times in ms.  The first slowed on
    stage 1 (a sleep of 10 ms); a phase detected at 95 ms commits at
    160 ms, another starts at 180 ms and is still open; one compile at
    5 ms inside the window, one at 250 ms after it."""
    spans = {
        "engine.query": [(0, 100, dict(query=0, trial=0)),
                         (100, 200, dict(query=1, trial=1))],
        "executor.bounds": [(0, 10, dict(syncs=4)),
                            (100, 110, dict(syncs=4))],
        "executor.embed": [(10, 20, dict(syncs=1)),
                           (110, 120, dict(syncs=1))],
        "executor.stage": [(20, 50, dict(stage=0, blocks=2, syncs=1)),
                           (50, 70, dict(stage=1, blocks=2, syncs=1)),
                           (120, 150, dict(stage=0, blocks=2, syncs=1)),
                           (150, 170, dict(stage=1, blocks=2, syncs=1))],
        "executor.interference": [(70, 80, dict(stage=1,
                                                factor_pct=150))],
        "executor.head": [(80, 95, dict(syncs=1)),
                          (170, 190, dict(syncs=1))],
        "rebalance.detect": [(95, 95, {}), (180, 180, {})],
        "rebalance.commit": [(160, 160, dict(changed=1, trials=2))],
        "jax.compile": [(5, 5, dict(ms=3)), (250, 250, dict(ms=3))],
    }
    ps = progspans.ProgramSpans(
        spans={k: iv(*[(a, b) for a, b, _ in v]) for k, v in spans.items()},
        meta={k: [m for _, _, m in v] for k, v in spans.items()},
        modules=iv((13, 18), (24, 48), (52, 68), (83, 93),
                   (113, 118), (124, 148), (152, 168), (173, 188)))
    # Operations fill every module run but for a 2 ms gap at 30-32 ms.
    busy = iv((13, 18), (24, 30), (32, 48), (52, 68), (83, 93),
              (113, 118), (124, 148), (152, 168), (173, 188))
    profile = devtrace.Profile(busy=[busy], op_ns={}, module_ns={},
                               spans={"api.run": iv((0, 200)),
                                      "run_query": iv((0, 100),
                                                      (100, 200))})
    return ps, types.SimpleNamespace(profile=profile)


def test_readers_match_sums_by_hand(monkeypatch):
    ps, run = synthetic()
    monkeypatch.setattr(progspans, "of", lambda r: ps)
    got = {m: bench.metric_reader(m)(run) for m in READERS}
    # Idle outside module runs inside the launch spans, per query:
    # embed 10-5, stage 0 30-24, stage 1 20-16, head 15-10 (first
    # query) and 10-5, 30-24, 20-16, 20-15 (second): 20 ms each.
    assert got == {"executor.host_syncs": 8.0,         # 4 + 1 + 2 + 1
                   "executor.bounds_ms": 10.0,
                   "executor.launch_idle_ms": 20.0,
                   "rebalance.detections": 2,
                   "rebalance.explore_s": pytest.approx(0.065),
                   "runloop.compiles": 1}


def test_idle_split_by_hand():
    ps, run = synthetic()
    split = progspans.idle_split(ps, run.profile.busy[0],
                                 run.profile.spans["run_query"])
    assert {k: v / MS for k, v in split.items()} == {
        "module gaps": 2, "executor.bounds": 20, "launch": 40,
        "executor.interference": 10, "outside program spans": 5 + 10}


def test_readers_report_nothing_without_program_spans(monkeypatch):
    # The benchmark's own trace (no program spans), as a parent
    # without them would leave.
    tmp = DATA / "sample.xplane.pb"
    monkeypatch.setattr(progspans, "find_xplane", lambda d: str(tmp))
    run = types.SimpleNamespace(
        profile=devtrace.load(str(tmp), bench.SPANS))
    assert progspans.of(run) is None
    assert all(bench.metric_reader(m)(run) is None for m in READERS)
    assert progspans.of(types.SimpleNamespace(profile=None)) is None


# -- the trace recorded on the chip ----------------------------------------


def raw_events():
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(str(PROGRAM)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(ev)
    return out


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("trace") / "plugins" / "profile"
    trace_dir.mkdir(parents=True)
    shutil.copy(PROGRAM, trace_dir / "host.xplane.pb")
    run = types.SimpleNamespace(profile=devtrace.load(str(PROGRAM),
                                                      bench.SPANS))
    return run, trace_dir.parents[1]


def test_recorded_metadata_as_profile_data_gives_it():
    ev = raw_events()
    stages = [list(e.stats) for e in ev["executor.stage"]]
    assert ("stage", 2) in stages[2] and ("syncs", 1) in stages[2]
    assert [list(e.stats) for e in ev["executor.bounds"]][0] == \
        [("syncs", 8)]
    assert {dict(e.stats)["factor_pct"]
            for e in ev["executor.interference"]} == {500}
    assert sorted(dict(e.stats)["query"] for e in ev["engine.query"]) == \
        [0, 0, 1, 1, 2, 2]


def test_readers_on_the_recorded_trace(recorded, monkeypatch):
    run, trace_dir = recorded
    monkeypatch.setattr(progspans, "TRACE_DIR", trace_dir)
    ps = progspans.of(run)
    assert ps is progspans.of(run)                 # one load per file
    got = {m: bench.metric_reader(m)(run) for m in READERS}
    ev = raw_events()
    n = len(ev["engine.query"])
    assert n == 6
    syncs = sum(dict(e.stats)["syncs"] for name in progspans.SYNCING
                for e in ev[name])
    assert got["executor.host_syncs"] == syncs / n == 3 * 4 + 2
    assert got["executor.bounds_ms"] == pytest.approx(
        1e-6 * sum(e.duration_ns for e in ev["executor.bounds"]) / n)
    launch_ms = 1e-6 * sum(e.duration_ns for name in progspans.LAUNCH
                           for e in ev[name]) / n
    assert 0 < got["executor.launch_idle_ms"] < launch_ms
    detects = sorted(e.start_ns for e in ev["rebalance.detect"])
    commits = sorted(e.start_ns for e in ev["rebalance.commit"])
    assert got["rebalance.detections"] == len(detects) >= 1
    waits = [min(c for c in commits if c >= d) - d for d in detects
             if any(c >= d for c in commits)]
    assert waits
    assert got["rebalance.explore_s"] == pytest.approx(
        1e-9 * sum(waits) / len(waits))
    a, b = run.profile.window("api.run")
    assert got["runloop.compiles"] == sum(
        a <= e.start_ns <= b for e in ev.get("jax.compile", []))


def test_idle_split_adds_up_on_the_recorded_trace(recorded):
    run, _ = recorded
    ps = progspans.load(str(PROGRAM))
    busy = run.profile.busy[0]
    within = run.profile.spans["run_query"]
    split = progspans.idle_split(ps, busy, within)
    idle = sum((e - s) - devtrace.covered(busy, int(s), int(e))
               for s, e in devtrace.union(within))
    assert sum(split.values()) == idle
    assert all(v >= 0 for v in split.values())
    assert split["launch"] > 0 and split["executor.bounds"] > 0
    assert split["executor.interference"] > 0


# -- a whole traced run -----------------------------------------------------


@pytest.mark.parametrize("name", ["qwen3-4b.interfere", "qwen3-4b.steady"])
def test_traced_run_reports_the_program_counts(name):
    cell, cfg = small_cell(name)
    res = bench.run_cell(cell, 2**31 + 7, 1.5, True, require_tpu=False,
                         program_config=cfg)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["executor.host_syncs"] == 3 * 4 + 2
    assert m["runloop.compiles"] == 0
    assert m["executor.bounds_ms"] > 0
    assert m["rebalance.detections"] >= 0
    assert not bench.TRACE_DIR.exists()
