"""A whole run on the CPU at smoke widths, past the look for a chip,
with the timed path broken underneath: ``correct`` has to come out
false for each fault a serving cell can have, and true without one."""
import pytest

import run as bench
from repro.configs import get_smoke_config
from repro.pipeline.executor import LocalPipelineExecutor

SMALL = {
    "qwen3-4b": dict(hidden_size=256, num_hidden_layers=2,
                     num_attention_heads=4, num_key_value_heads=2,
                     head_dim=64, intermediate_size=512, vocab_size=512),
    "mamba2-370m": dict(d_model=256, n_layer=2, d_state=32,
                        vocab_size=512),
}


class SkipsAStage(LocalPipelineExecutor):
    """The stage that starts at block 1 hands back its input unchanged."""

    def __init__(self, cfg, params):
        super().__init__(cfg, params)
        inner = self._stage_fn

        def stage_fn(params, x, positions, lo, hi):
            if int(lo) == 1:
                return x
            return inner(params, x, positions, lo, hi)

        self._stage_fn = stage_fn


class AltersAnAnswer(LocalPipelineExecutor):
    """The head's logits at one position are negated, so the token they
    put first is the one they ranked last."""

    def head(self, x):
        logits = super().head(x)
        return logits.at[:, 5].set(-logits[:, 5])


def small_cell(name):
    cell = bench.load_cell(name)
    arch = cell.config["arch"]
    cell.config = {**cell.config, **SMALL[arch], "program": {}}
    cell.traffic = {**cell.traffic, "prompt_tokens": 64}
    if "interference" in cell.traffic:
        cell.traffic["interference"] = {
            **cell.traffic["interference"], "first_onset_s": 0.2,
            "on_s": 0.4, "off_s": 0.4}
    return cell, get_smoke_config(arch)


@pytest.mark.parametrize("name", ["qwen3-4b.interfere", "qwen3-4b.steady"])
@pytest.mark.parametrize("fault,correct", [
    (None, True), (SkipsAStage, False), (AltersAnAnswer, False)],
    ids=["sound", "stage_unchanged", "answer_altered"])
def test_correct_reads_the_timed_path(name, fault, correct):
    cell, cfg = small_cell(name)
    res = bench.run_cell(cell, 2**31 + 99, 1.5, False, require_tpu=False,
                         executor_base=fault or LocalPipelineExecutor,
                         program_config=cfg)
    assert res["correct"] is correct, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"tokens_per_s", "latency_p50_ms",
                                   "latency_p95_ms", "setup_s"}


def test_no_tpu_exits_non_zero_and_prints_no_result(capsys):
    cell, cfg = small_cell("qwen3-4b.steady")
    with pytest.raises(SystemExit) as e:
        bench.run_cell(cell, 1, 1.0, False, program_config=cfg)
    assert e.value.code != 0
    assert capsys.readouterr().out == ""


def test_interference_schedule_follows_the_wall_clock():
    spec = {"first_onset_s": 2.0, "on_s": 4.0, "off_s": 4.0,
            "eps": [1, 3, 0, 2], "factor": 3.0}
    s = bench.Interference(spec, 4)
    got = [s.period(t) for t in (0.0, 1.99, 2.0, 5.99, 6.0, 9.99, 10.0,
                                 18.5, 26.0)]
    assert got == [None, None, 0, 0, None, None, 1, 2, 3]
    s.t0 = 100.0
    assert s.onsets(120.0) == [(102.0, 106.0, 1), (110.0, 114.0, 3),
                               (118.0, 122.0, 0)]
    assert bench.Interference(None, 4).onsets(1e9) == []
