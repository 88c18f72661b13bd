"""The control at a size a CPU test run holds: the reference computed
with fp8 operands, put in the served path's place, has to read far
above what the served bf16 path reads, on every seed.

The check's number is ``run.widest_gap``: the widest gap by which the
f32 reference's logit of the token put first lies below its best.
At full size the readings and the limit are in PERF.md (measured on
the chip with ``bench/calibrate.py``)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import run as bench
from reference import mamba2, qwen3
from reference.common import weight_key
from repro.configs import get_smoke_config
from repro.core.pipeline_state import balanced_config
from repro.pipeline.executor import LocalPipelineExecutor

QWEN3 = dict(hidden_size=256, num_hidden_layers=4, num_attention_heads=4,
             num_key_value_heads=2, head_dim=64, intermediate_size=512,
             vocab_size=2048, rope_theta=1e6, rms_norm_eps=1e-6)
MAMBA2 = dict(d_model=256, n_layer=4, expand=2, d_state=32, d_conv=4,
              headdim=64, vocab_size=2048, norm_epsilon=1e-6)


def readings(ref, c, arch, seed, seq=256):
    cfg = dataclasses.replace(get_smoke_config(arch), num_layers=4,
                              vocab_size=2048)
    w = ref.init_weights(c, weight_key(seed), jnp.bfloat16)
    tokens = jnp.asarray(
        np.random.default_rng(seed).integers(0, 2048, (1, seq)), jnp.int32)
    exact = ref.make_forward(c)(w, tokens[0])
    low = ref.make_forward(c, quant=True)(w, tokens[0])
    logits, _ = LocalPipelineExecutor(cfg, w).run_query(
        tokens, balanced_config(cfg.num_blocks, 4))
    served = jnp.argmax(logits[0], axis=-1)
    return (float(bench.widest_gap(exact, served)),
            float(bench.widest_gap(exact, jnp.argmax(low, axis=-1))))


@pytest.mark.parametrize("ref,c,arch", [(qwen3, QWEN3, "qwen3-4b"),
                                        (mamba2, MAMBA2, "mamba2-370m")],
                         ids=["qwen3", "mamba2"])
def test_fp8_control_reads_three_times_the_served_path(ref, c, arch):
    for seed in (1, 2, 3):
        program, control = readings(ref, c, arch, seed)
        assert control >= 3 * program, (seed, program, control)
