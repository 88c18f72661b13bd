"""Pipeline executor + SPMD schedule tests."""
import dataclasses
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import Model
from repro.pipeline import LocalPipelineExecutor, MeasuredTimeSource, stage_bounds


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), num_layers=6)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    return cfg, model, params


def test_stage_bounds():
    assert stage_bounds([2, 0, 3]) == [(0, 2), (2, 2), (2, 5)]


def test_executor_matches_model(setup):
    """Pipeline-partitioned execution == monolithic forward, any config."""
    cfg, model, params = setup
    ex = LocalPipelineExecutor(cfg, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                                cfg.vocab_size)
    ref_logits, _ = model.forward(params, tokens=tokens)
    for config in ([2, 2, 2], [1, 3, 2], [6], [3, 0, 3], [1, 1, 1, 1, 1, 1]):
        logits, times = ex.run_query(tokens, config)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits),
                                   atol=1e-4, rtol=1e-4)
        assert times.shape == (len(config),)
        assert np.all(times[np.asarray(config) > 0] > 0)


@pytest.mark.parametrize("slowdowns", [None, [1.0, 3.0, 1.0]],
                         ids=["clean", "slowed"])
@pytest.mark.parametrize("config", [[2, 2, 2], [1, 3, 2]])
def test_run_query_logits_equal_model_bit_for_bit(setup, config, slowdowns):
    """Dispatching ahead moves only the host's waits: the same programs
    run on the same inputs in the same order."""
    cfg, model, params = setup
    ex = LocalPipelineExecutor(cfg, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                                cfg.vocab_size)
    ref_logits, _ = model.forward(params, tokens=tokens)
    logits, _ = ex.run_query(tokens, config, slowdowns)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(ref_logits))


class _Output:
    """A program's output that logs when the host waits for it."""

    def __init__(self, value, tag, log):
        self.value, self.tag, self.log = value, tag, log

    def block_until_ready(self):
        self.value.block_until_ready()
        self.log.append(("ready", self.tag))
        return self


def _logging_executor(cfg, params, log):
    """An executor whose stage and head programs log their dispatches
    and hand back :class:`_Output`s."""
    ex = LocalPipelineExecutor(cfg, params)
    stage_fn, head_fn = ex._stage_fn, ex._head_fn
    unwrap = lambda x: x.value if isinstance(x, _Output) else x

    def logged_stage(params, x, positions, lo, hi):
        tag = f"s{sum(e[0] == 'dispatch' and e[1] != 'head' for e in log)}"
        log.append(("dispatch", tag))
        return _Output(stage_fn(params, unwrap(x), positions, lo, hi), tag,
                       log)

    def logged_head(params, x):
        log.append(("dispatch", "head"))
        return _Output(head_fn(params, unwrap(x)), "head", log)

    ex._stage_fn, ex._head_fn = logged_stage, logged_head
    return ex


def test_run_query_dispatches_ahead_except_around_a_slowed_stage(
        setup, monkeypatch):
    cfg, _, params = setup
    config = [2, 1, 1, 2]
    tokens = jnp.zeros((1, 32), jnp.int32)
    log = []
    ex = _logging_executor(cfg, params, log)
    ex.warmup(1, 32)
    real_sleep, slept = time.sleep, []

    def sleep(seconds):
        log.append(("sleep", None))
        slept.append(seconds)
        real_sleep(seconds)
        log.append(("woke", None))

    monkeypatch.setattr(time, "sleep", sleep)
    at = log.index

    # Clean: every next program is queued before the host waits.
    log.clear()
    t0 = time.perf_counter()
    _, times = ex.run_query(tokens, config)
    wall = time.perf_counter() - t0
    order = ["s0", "s1", "s2", "s3", "head"]
    for cur, nxt in zip(order, order[1:]):
        assert at(("dispatch", nxt)) < at(("ready", cur)), (cur, nxt)
    assert np.all(times > 0) and times.sum() <= wall
    assert not slept

    # EP 1 slowed 3x: its stage waits for stage 0, and stage 2 for the
    # end of its sleep; the rest still run ahead.
    log.clear()
    t0 = time.perf_counter()
    _, times = ex.run_query(tokens, config, [1.0, 3.0, 1.0, 1.0])
    wall = time.perf_counter() - t0
    assert at(("ready", "s0")) < at(("dispatch", "s1"))
    assert at(("ready", "s1")) < at(("sleep", None))
    assert at(("woke", None)) < at(("dispatch", "s2"))
    assert at(("dispatch", "s3")) < at(("ready", "s2"))
    assert at(("dispatch", "head")) < at(("ready", "s3"))
    (extra,) = slept
    assert times[1] == pytest.approx(extra * 3 / 2)
    assert np.all(times > 0) and times.sum() <= wall


def test_executor_no_recompile_across_configs(setup):
    """Dynamic boundaries: one compiled stage_fn serves every config."""
    cfg, model, params = setup
    ex = LocalPipelineExecutor(cfg, params)
    tokens = jnp.zeros((1, 32), jnp.int32)
    ex.run_query(tokens, [3, 3])
    n0 = ex._stage_fn._cache_size()
    for config in ([2, 4], [1, 5], [6, 0], [4, 2]):
        ex.run_query(tokens, config)
    assert ex._stage_fn._cache_size() == n0


def test_measured_time_source():
    src = MeasuredTimeSource(np.array([1.0, 2.0, 3.0, 4.0]),
                             np.array([1.0, 2.0]))
    t = src.stage_times([2, 2])
    assert t[0] == pytest.approx(3.0)
    assert t[1] == pytest.approx(14.0)   # (3+4) * 2.0


def test_spmd_pipeline_subprocess():
    """GPipe shard_map schedule on 4 host devices == monolithic forward,
    incl. uneven and empty-stage configs (run in a subprocess because
    XLA_FLAGS must be set before JAX initializes)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import Model
from repro.models.layers import embed
import repro.models.blocks as blk
from repro.pipeline.spmd import pipelined_forward
from repro.launch.mesh import make_stage_mesh

cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), num_layers=8)
model = Model(cfg)
params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
mesh = make_stage_mesh(4)
B, S, M = 2, 32, 4
tokens = jax.random.randint(jax.random.PRNGKey(1), (M, B, S), 0,
                            cfg.vocab_size)
inputs = jax.vmap(lambda t: embed(params["embed"], t))(tokens)
pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

def ref(t):
    x = embed(params["embed"], t)
    def body(c, bp):
        h, _ = blk.block_forward(bp, cfg, c, pos)
        return h, None
    h, _ = jax.lax.scan(body, x, params["blocks"])
    return h
refs = np.stack([np.asarray(ref(tokens[m])) for m in range(M)])
for config in ([2,2,2,2], [1,3,2,2], [3,0,3,2]):
    with mesh:
        out = pipelined_forward(cfg, mesh, params["blocks"], config,
                                inputs, cap=4)
    err = np.max(np.abs(np.asarray(out) - refs))
    assert err < 1e-4, (config, err)
print("OK")
"""
    root = pathlib.Path(__file__).resolve().parents[1]
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True,
                       env={"PYTHONPATH": str(root / "src"),
                            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
                            "HOME": os.environ.get("HOME", "/tmp"),
                            # host-device run: skip accelerator probing
                            "JAX_PLATFORMS": "cpu"}, cwd=str(root))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
