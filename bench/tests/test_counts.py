"""The operation and byte counts from shapes, against sums by hand."""
import json

import jax.numpy as jnp

from run import BENCH
from reference import mamba2, qwen3


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_qwen3_4b_counts_at_1024_tokens():
    got = qwen3.costs(_config("qwen3-4b"), 1024, jnp.bfloat16)
    # q, k, v: 2 * 1024 * 2560 * (4096 + 1024 + 1024); o: 2 * 1024 * 4096
    # * 2560; MLP: 3 * 2 * 1024 * 2560 * 9728; causal attention: 2 * 2 *
    # 32 heads * 128 * (1024 * 1025 / 2) pairs.
    proj = 32_212_254_720 + 21_474_836_480
    mlp = 153_008_209_920
    attn = 8_598_323_200
    assert got["block_flops"] == proj + mlp + attn
    # wq 2560x4096, wk and wv 2560x1024, wo 4096x2560, MLP 3x2560x9728,
    # two norms of 2560 and q/k norms of 128: 100,930,816 bf16 values.
    assert got["block_bytes"] == 2 * 100_930_816
    assert got["act_bytes"] == 1024 * 2560 * 2
    assert got["head_flops"] == 2 * 1024 * 2560 * 151_936
    assert got["num_blocks"] == 36
    per_query = 36 * got["block_flops"] + got["head_flops"]
    assert abs(per_query / 1e12 - 8.55) < 0.01


def test_mamba2_370m_counts_at_2048_tokens():
    got = mamba2.costs(_config("mamba2-370m"), 2048, jnp.bfloat16)
    # projections to z, x (2048 each), B, C (128 each), dt (32 heads):
    # 2 * 2048 * 1024 * 4384; out_proj 2 * 2048 * 2048 * 1024; conv
    # 2 * 2048 * 4 * 2304; recurrence 4 * 2048 * 32 * 64 * 128.
    proj = 18_387_828_736 + 8_589_934_592
    conv = 37_748_736
    ssm = 2_147_483_648
    assert got["block_flops"] == proj + conv + ssm
    # bf16: wz, wx 1024x2048; wB, wC 1024x128; wdt 1024x32; conv_w
    # 4x2304; conv_b 2304; norm 2048; out_proj 2048x1024; ln1 1024
    # (6,600,960 values); f32: A_log, D, dt_bias, 32 each.
    assert got["block_bytes"] == 2 * 6_600_960 + 4 * 96
    assert got["act_bytes"] == 2048 * 1024 * 2
    assert got["head_flops"] == 2 * 2048 * 1024 * 50_280
    assert got["num_blocks"] == 48
