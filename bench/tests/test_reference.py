"""The plain references against the program's ``Model.forward`` at
smoke widths, in f32 on the CPU, on the benchmark's own weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference import mamba2, qwen3
from reference.common import weight_key
from repro.configs import get_smoke_config
from repro.models import Model

QWEN3 = dict(hidden_size=256, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=64, intermediate_size=512,
             vocab_size=512, rope_theta=1e6, rms_norm_eps=1e-6)
MAMBA2 = dict(d_model=256, n_layer=2, expand=2, d_state=32, d_conv=4,
              headdim=64, vocab_size=512, norm_epsilon=1e-6)
CASES = [(qwen3, QWEN3, "qwen3-4b"), (mamba2, MAMBA2, "mamba2-370m")]


@pytest.mark.parametrize("ref,c,arch", CASES, ids=["qwen3", "mamba2"])
def test_reference_matches_the_program_in_f32(ref, c, arch):
    cfg = get_smoke_config(arch)
    w = ref.init_weights(c, weight_key(3), jnp.float32)
    # The benchmark's weights have the program's layout, leaf for leaf.
    shapes = jax.eval_shape(
        lambda: Model(cfg).init_params(jax.random.PRNGKey(0), jnp.float32))
    assert jax.tree.structure(shapes) == jax.tree.structure(w)
    for a, b in zip(jax.tree.leaves(shapes), jax.tree.leaves(w)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 512, (1, 64)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        prog = Model(cfg).forward(w, tokens)[0][0]
    got = ref.make_forward(c)(w, tokens[0])
    scale = float(jnp.max(jnp.abs(got)))
    # Both sides are f32 at full matmul precision; what is left is the
    # order of summation (the program's chunked SSD and online softmax).
    assert float(jnp.max(jnp.abs(prog - got))) <= 1e-5 * scale


@pytest.mark.parametrize("ref,c,arch", CASES, ids=["qwen3", "mamba2"])
def test_fp8_control_departs_from_the_reference(ref, c, arch):
    w = ref.init_weights(c, weight_key(4), jnp.float32)
    tokens = jnp.arange(64, dtype=jnp.int32)
    exact = ref.make_forward(c)(w, tokens)
    low = ref.make_forward(c, quant=True)(w, tokens)
    assert float(jnp.max(jnp.abs(low - exact))) > 1e-2 * float(
        jnp.max(jnp.abs(exact)))
