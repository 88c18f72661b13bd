"""Host spans and markers of the live served path, read back from a
profiler trace (docs/TELEMETRY.md "Spans"), and the named scopes of the
stage program.  Counts and nesting only: no timing is asserted."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs import get_smoke_config
from repro.models import Model
from repro.pipeline.executor import LocalPipelineExecutor
from repro.serving import ServingEngine

SEQ = 32
EPS = 4
SLOW = [1.0, 5.0, 1.0, 1.0]


def read_spans(directory):
    """``{name: [(start_ns, end_ns, {stat: value})]}`` of the host
    events under ``directory``'s newest trace."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.end_ns, dict(ev.stats)))
    return out


def schedule(q):
    """Clean for the first query, which arms the detector; EP 1 slowed
    5x from then on, so the detector fires and ODIN explores."""
    return [1.0] * EPS if q == 0 else list(SLOW)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Eight queries through ``repro.api.run`` on a warm 4-EP engine
    under the profiler, on ``schedule``."""
    cfg = dataclasses.replace(get_smoke_config("qwen2-0.5b"), num_layers=8)
    params = Model(cfg).init_params(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(0)
    queries = [jnp.asarray(rng.integers(0, cfg.vocab_size, (1, SEQ)),
                           jnp.int32) for _ in range(8)]
    eng = ServingEngine(cfg, params, num_eps=EPS, scheduler="odin",
                        alpha=2)
    eng.executor.warmup(1, SEQ)
    rt = eng.runtime
    before = (rt.num_rebalances, len(rt.mitigation_lengths))
    out_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out_dir))
    try:
        trace = api.run(api.RunSpec(engine=eng, queries=queries,
                                    schedule=schedule))
    finally:
        jax.profiler.stop_trace()
    after = (rt.num_rebalances, len(rt.mitigation_lengths))
    return trace, read_spans(out_dir), before, after


def test_one_engine_query_span_per_query(served):
    trace, spans, _, _ = served
    queries = spans["engine.query"]
    assert len(queries) == len(trace.configs) == 8
    assert [m["query"] for _, _, m in queries] == list(range(8))
    assert [bool(m["trial"]) for _, _, m in queries] == \
        [bool(s) for s in trace.serial_mask]


def test_executor_spans_nest_in_their_query_and_count_syncs(served):
    _, spans, _, _ = served
    queries = spans["engine.query"]
    syncs = [0] * len(queries)
    names = ("executor.bounds", "executor.embed", "executor.stage",
             "executor.interference", "executor.head")
    for name in names:
        for s, e, meta in spans[name]:
            inside = [i for i, (a, b, _) in enumerate(queries)
                      if a <= s and e <= b]
            assert len(inside) == 1, (name, s, e)
            syncs[inside[0]] += meta.get("syncs", 0)
    # The embedding, each stage, the head; the bounds are committed once
    # per executor, so their span waits for nothing.
    assert syncs == [EPS + 2] * len(queries)
    assert [m for _, _, m in spans["executor.bounds"]] == \
        [{"syncs": 0}] * len(queries)
    waits = [m for _, _, m in spans["executor.stage"] if m["syncs"] == 1]
    assert len(waits) == EPS * len(queries)


def test_formed_dispatch_with_a_join(tmp_path):
    """A formed dispatch is one ``engine.query``, launch to drain; a
    query joining at a stage boundary adds an embedding (1 sync) and a
    fused catch-up with its splice (2), all inside it."""
    from repro.serving.engine import _LiveQueryExecutor

    cfg = dataclasses.replace(get_smoke_config("qwen2-0.5b"), num_layers=8)
    params = Model(cfg).init_params(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(1)
    queries = [jnp.asarray(rng.integers(0, cfg.vocab_size, (1, SEQ)),
                           jnp.int32) for _ in range(2)]
    eng = ServingEngine(cfg, params, num_eps=EPS, scheduler="odin")
    for rows in (1, 2):
        eng.executor.ensure_warm(rows, SEQ)
    live = _LiveQueryExecutor(eng, queries, lambda q: list(SLOW))
    live.begin_query(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        dispatch = live.begin_dispatch(0, eng.runtime.steady_step())
        dispatch.add(0)
        dispatch.next_boundary()
        dispatch.join(1)
        dispatch.finish()
    finally:
        jax.profiler.stop_trace()
    spans = read_spans(tmp_path)
    (a, b, meta), = spans["engine.query"]
    assert meta == {"query": 0, "trial": 0}
    syncs = 0
    for name in ("executor.bounds", "executor.embed", "executor.stage",
                 "executor.interference", "executor.head"):
        for s, e, m in spans.get(name, []):
            assert a <= s and e <= b, name
            syncs += m.get("syncs", 0)
    assert len(spans["executor.embed"]) == 2
    assert syncs == EPS + 2 + 3
    assert [m for _, _, m in spans["executor.bounds"]] == [{"syncs": 0}]
    fused = [m for _, _, m in spans["executor.stage"] if m["syncs"] == 2]
    assert fused == [{"stage": 0, "blocks": 2, "syncs": 2}]


def test_interference_spans_only_on_slowed_stages(served):
    _, spans, _, _ = served
    slowed = [i for i, f in enumerate(SLOW) if f > 1.0]
    got = spans["executor.interference"]
    assert len(got) == len(slowed) * (len(spans["engine.query"]) - 1)
    for _, _, meta in got:
        assert meta["stage"] in slowed
        assert meta["factor_pct"] == round(100 * SLOW[meta["stage"]])
    # The sleep lies outside every stage span.
    for s, e, _ in got:
        assert all(e <= a or b <= s
                   for a, b, _ in spans["executor.stage"])


def test_rebalance_marks_match_the_runtime_counters(served):
    trace, spans, before, after = served
    detects = spans.get("rebalance.detect", [])
    commits = spans.get("rebalance.commit", [])
    assert len(detects) == after[0] - before[0] >= 1
    assert len(commits) == after[1] - before[1] >= 1
    for _, _, meta in commits:
        assert meta["changed"] in (0, 1) and meta["trials"] >= 1


def test_no_compile_marks_on_warm_shapes(served):
    _, spans, _, _ = served
    assert "jax.compile" not in spans


def test_one_compile_mark_for_a_first_seen_shape(tmp_path):
    # The listener is registered by repro.telemetry.spans, which the
    # serving engine imports.
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0)
    x = np.ones((7, 5), np.float32)
    for name, calls in (("cold", 1), ("warm", 0)):
        jax.profiler.start_trace(str(tmp_path / name))
        try:
            f(x).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        marks = read_spans(tmp_path / name).get("jax.compile", [])
        assert len(marks) == calls, name
        assert all(m["ms"] >= 0 for _, _, m in marks)


@pytest.mark.parametrize("arch,scopes", [
    ("qwen2-0.5b", ("stage", "attention", "mlp")),
    ("mamba2-370m", ("stage", "ssd")),
])
def test_stage_program_carries_named_scopes(arch, scopes):
    cfg = get_smoke_config(arch)
    params = jax.eval_shape(
        lambda: Model(cfg).init_params(jax.random.PRNGKey(0), jnp.float32))
    ex = LocalPipelineExecutor(cfg, params)
    x = jax.ShapeDtypeStruct((1, 16, cfg.d_model), jnp.float32)
    positions = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    bound = jax.ShapeDtypeStruct((), jnp.int32)
    text = ex._stage_fn.lower(params, x, positions, bound,
                              bound).as_text(debug_info=True)
    for scope in scopes:
        assert f"/{scope}/" in text, scope
