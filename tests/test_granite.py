"""Granite-4.0-H-Micro, a hybrid of Mamba-2 and attention layers, at
smoke widths on the CPU: the program against the benchmark's plain f32
reference, the executor against ``Model.forward`` under stage splits
that cut inside the layer period, the Mamba-2 scan's decays against the
recurrence, and the one-kind models' stage program left without a
conditional."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import Model
from repro.models.mamba2 import ssd_chunked
from repro.pipeline.executor import LocalPipelineExecutor
from repro.schedulers.runtime import layers_moved

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from reference import granite  # noqa: E402
from reference.common import weight_key  # noqa: E402

ARCH = "granite-4.0-h-micro"


def smoke_reference_config():
    """The benchmark's configuration at the smoke config's widths: one
    period of the published layer types."""
    c = json.loads((BENCH / "configs" / f"{ARCH}.json").read_text())
    cfg = get_smoke_config(ARCH)
    s = cfg.ssm
    c.update(hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
             num_attention_heads=cfg.num_heads,
             num_key_value_heads=cfg.num_kv_heads,
             shared_intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size,
             mamba_d_state=s.d_state, mamba_d_head=s.head_dim,
             mamba_n_heads=s.num_heads(cfg.d_model),
             mamba_chunk_size=s.chunk_size,
             layer_types=c["layer_types"][:cfg.num_layers])
    return cfg, c


@pytest.fixture(scope="module")
def smoke():
    cfg, c = smoke_reference_config()
    weights = granite.init_weights(c, weight_key(5), jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 64)), jnp.int32)
    return cfg, c, weights, tokens


def test_config_counts_one_block_per_layer():
    cfg = get_config(ARCH)
    assert cfg.num_blocks == 40
    kinds = [cfg.layer_kind(i) for i in range(cfg.num_layers)]
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    c = json.loads((BENCH / "configs" / f"{ARCH}.json").read_text())
    assert kinds == ["attn" if t == "attention" else t
                     for t in c["layer_types"]]


def test_reference_weights_have_the_program_layout(smoke):
    cfg, _, weights, _ = smoke
    shapes = jax.eval_shape(
        lambda: Model(cfg).init_params(jax.random.PRNGKey(0), jnp.float32))
    assert jax.tree.structure(shapes) == jax.tree.structure(weights)
    for a, b in zip(jax.tree.leaves(shapes), jax.tree.leaves(weights)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_forward_matches_the_reference_in_f32(smoke):
    cfg, c, weights, tokens = smoke
    with jax.default_matmul_precision("highest"):
        prog = Model(cfg).forward(weights, tokens)[0][0]
    got = granite.make_forward(c)(weights, tokens[0])
    scale = float(jnp.max(jnp.abs(got)))
    # Both sides are f32 at full matmul precision; what is left is the
    # order of summation (the program's chunked SSD and online softmax).
    assert float(jnp.max(jnp.abs(prog - got))) <= 1e-5 * scale


def test_fp8_control_departs_from_the_reference(smoke):
    _, c, weights, tokens = smoke
    exact = granite.make_forward(c)(weights, tokens[0])
    low = granite.make_forward(c, quant=True)(weights, tokens[0])
    assert float(jnp.max(jnp.abs(low - exact))) > 1e-2 * float(
        jnp.max(jnp.abs(exact)))


# Ten layers, attention at 5.  [3, 4, 3]: stage 0 holds only Mamba-2
# layers, stage 1 the attention layer; every split cuts inside the period.
SPLITS = [[3, 4, 3], [5, 1, 4], [0, 6, 4], [2, 2, 3, 3], [1, 8, 1]]


@pytest.mark.parametrize("split", SPLITS, ids=str)
def test_run_query_logits_equal_model_bit_for_bit(smoke, split):
    cfg, _, weights, tokens = smoke
    ex = LocalPipelineExecutor(cfg, weights)
    ref, _ = Model(cfg).forward(weights, tokens)
    logits, times = ex.run_query(tokens, split)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref))
    assert times.shape == (len(split),)


def test_stage_meta_counts_each_kind(smoke):
    cfg, _, weights, _ = smoke
    ex = LocalPipelineExecutor(cfg, weights)
    assert ex._stage_meta(1, 3, 7) == {"stage": 1, "blocks": 4, "lo": 3,
                                       "hi": 7, "attn": 1, "mamba": 3}
    assert ex._stage_meta(0, 0, 3)["attn"] == 0


@pytest.mark.parametrize("arch,switch", [("qwen3-4b", False),
                                         ("mamba2-370m", False),
                                         (ARCH, True)])
def test_stage_program_switches_only_for_mixed_layers(arch, switch):
    """A model of one layer kind keeps its stage body without a
    conditional; a hybrid's one program picks the kind per layer."""
    cfg = get_smoke_config(arch)
    params = jax.eval_shape(
        lambda: Model(cfg).init_params(jax.random.PRNGKey(0), jnp.float32))
    ex = LocalPipelineExecutor(cfg, params)
    x = jax.ShapeDtypeStruct((1, 32, cfg.d_model), jnp.float32)
    positions = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    bound = jax.ShapeDtypeStruct((), jnp.int32)
    text = ex._stage_fn.lower(params, x, positions, bound, bound).as_text()
    assert ("stablehlo.case" in text) == switch


def test_layers_moved_counts_layers_that_change_stage():
    assert layers_moved([10, 10, 10, 10], [10, 10, 10, 10]) == 0
    assert layers_moved([10, 10, 10, 10], [11, 9, 10, 10]) == 1
    assert layers_moved([10, 10, 10, 10], [9, 11, 10, 10]) == 1
    assert layers_moved([10, 10, 10, 10], [8, 12, 9, 11]) == 3


def _recurrence(x, dt, A, B, C):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t``
    in f32, one token at a time."""
    f32 = jnp.float32

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (h * jnp.exp(dtt * A)[:, None, None]
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return h, jnp.einsum("hpn,n->hp", h, ct,
                             precision=jax.lax.Precision.HIGHEST)

    H, P = x.shape[1:]
    _, y = jax.lax.scan(step, jnp.zeros((H, P, B.shape[-1]), f32),
                        (x.astype(f32), dt, B.astype(f32), C.astype(f32)))
    return y


def test_ssd_decays_in_f32_match_the_recurrence():
    """The chunked scan in bf16 at a 256-token chunk, with steps and
    decays as the published model draws them (A in [-16, -1], dt in
    [1e-3, 1e-1]), within 3% of the f32 recurrence.  With the decays
    computed in bf16 the cumulative sum, near -100 at the chunk's end,
    carries an error of 0.25 and the same scan departs by tens of
    percent."""
    S, H, P, N = 512, 4, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    bf = jnp.bfloat16
    x = jax.random.normal(ks[0], (S, H, P), bf)
    B = jax.random.normal(ks[1], (S, N), bf)
    C = jax.random.normal(ks[2], (S, N), bf)
    dt = jnp.exp(jax.random.uniform(ks[3], (S, H), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    A = -jnp.linspace(1.0, 16.0, H)
    y, _ = ssd_chunked(x[None], dt[None], A, B[None], C[None], chunk=256)
    ref = _recurrence(x, dt, A, B, C)
    err = float(jnp.max(jnp.abs(y[0].astype(jnp.float32) - ref)))
    assert err <= 0.03 * float(jnp.max(jnp.abs(ref)))


def test_odin_moves_single_layers_of_the_hybrid():
    """``repro.api.run`` over a 4-EP engine of the hybrid with EP 1
    slowed 5x after the first query: ODIN explores and commits splits
    of single layers, and each commit's mark names the layers moved."""
    from repro import api
    from repro.launch.serve import build_engine
    from repro.core.pipeline_state import balanced_config

    cfg = get_smoke_config(ARCH)
    params = Model(cfg).init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = build_engine(cfg, params, [32], num_eps=4, scheduler="odin",
                       alpha=2)
    rng = np.random.default_rng(0)
    queries = [jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 32)),
                           jnp.int32) for _ in range(12)]
    trace = api.run(api.RunSpec(
        engine=eng, queries=queries,
        schedule=lambda q: [1.0] * 4 if q == 0 else [1.0, 5.0, 1.0, 1.0]))
    assert all(sum(c) == cfg.num_blocks == 10 for c in trace.configs)
    committed = [list(c) for c, s in zip(trace.configs, trace.serial_mask)
                 if not s]
    assert committed[-1] != balanced_config(10, 4)
    assert committed[-1][1] < balanced_config(10, 4)[1]
