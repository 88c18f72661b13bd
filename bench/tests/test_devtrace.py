"""The trace reduction: interval arithmetic against plain loops, and
the whole reduction on a small trace recorded on a TPU v5e
(``data/sample.xplane.pb``, made by ``record_trace.py``)."""
from pathlib import Path

import numpy as np
import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "data" / "sample.xplane.pb"


def naive_cover(intervals, a, b):
    """Nanoseconds of [a, b) covered by any interval, one ns at a time
    over the interval edges."""
    edges = sorted({a, b, *[x for iv in intervals for x in iv
                            if a <= x <= b]})
    total = 0
    for lo, hi in zip(edges, edges[1:]):
        mid = (lo + hi) / 2
        if any(s <= mid < e for s, e in intervals):
            total += hi - lo
    return total


@pytest.mark.parametrize("seed", range(5))
def test_interval_arithmetic_matches_loops(seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 1000, 40)
    iv = np.stack([starts, starts + rng.integers(1, 60, 40)], axis=1)
    merged = devtrace.union(iv)
    assert np.all(merged[1:, 0] > merged[:-1, 1])        # disjoint, sorted
    for a, b in ((0, 1100), (100, 400), (333, 334), (900, 2000)):
        want = naive_cover(iv.tolist(), a, b)
        assert devtrace.covered(merged, a, b) == want
        g = devtrace.gaps(merged, a, b)
        assert int(np.sum(g[:, 1] - g[:, 0])) == (b - a) - want
        assert all(naive_cover(iv.tolist(), int(s), int(e)) == 0
                   for s, e in g)


@pytest.fixture(scope="module")
def sample():
    return devtrace.load(str(DATA), ("run_query", "api.run"))


def test_recorded_trace_spans_and_device(sample):
    # record_trace.py: 3 api.run spans, 2 run_query spans in each.
    assert len(sample.spans["api.run"]) == 3
    assert len(sample.spans["run_query"]) == 6
    assert len(sample.busy) == 1                 # one chip ran anything
    # Six queries of three steps each ran the one jitted module.
    steps = [k for k in sample.module_ns if k.startswith("jit_matmul_step")]
    assert steps
    a, b = sample.window("api.run")
    busy = sample.busy_ns(a, b)
    assert 0 < busy < b - a
    # The host slept 5 ms after each step, inside run_query: the device
    # idles for at least that long each time.
    rq = devtrace.union(sample.spans["run_query"])
    idle_in_rq = sum((e - s) - devtrace.covered(sample.busy[0], s, e)
                     for s, e in rq)
    assert idle_in_rq >= 6 * 3 * 5e6
    idle = devtrace.idle_by_span(sample, a, b, ("run_query", "api.run"))
    # 20 ms after each query inside api.run, 30 ms between the calls.
    assert idle["api.run"] >= 6 * 20e6
    assert idle["outside spans"] >= 2 * 30e6
    assert abs(sum(idle.values()) - ((b - a) - busy)) < 1e3


def test_recorded_trace_busy_time_is_the_op_union(sample):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(DATA))
    ops = [(e.start_ns, e.end_ns) for p in data.planes
           if devtrace.DEVICE_PLANE.match(p.name) for line in p.lines
           if line.name == devtrace.OPS_LINE for e in line.events]
    a, b = sample.window("api.run")
    assert sample.busy_ns(a, b) == naive_cover(ops, a, b)


def test_self_time_leaves_out_nested_events():
    events = [(0, 100, "%while.1"), (10, 30, "%fusion.1"),
              (40, 90, "%fusion.2"), (50, 60, "%copy"), (120, 130, "%x")]
    got = dict(devtrace.self_times(events))
    assert got == {"%while.1": 30, "%fusion.1": 20, "%fusion.2": 40,
                   "%copy": 10, "%x": 10}
