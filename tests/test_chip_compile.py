"""Compile the served path and the Pallas kernels for a described TPU v5e.

Nothing runs: each case lowers and compiles at real widths for one chip
of a ``v5e:2x2`` topology, with the TPU compiler installed beside JAX,
so what the chip's compiler would refuse (a tile Mosaic cannot lay out,
a program that does not fit HBM) fails here at no chip time.  Interpret
mode cannot show either.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest-xdist workers each
import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import Model
from repro.pipeline.executor import LocalPipelineExecutor

#: One v5e chip's HBM; a served program must fit beside nothing else.
HBM_BYTES = 16e9
SEQ = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out.
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.fixture(scope="module")
def qwen3_4b(one_chip):
    """Qwen3-4B at published widths, bf16: the executor and the shapes
    its jitted stage/embed/head functions take."""
    cfg = get_config("qwen3-4b")
    shapes = jax.eval_shape(
        lambda k: Model(cfg).init_params(k, jnp.bfloat16),
        jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          shapes)
    x = _spec((1, SEQ, cfg.d_model), jnp.bfloat16, one_chip)
    ids = _spec((1, SEQ), jnp.int32, one_chip)
    bound = _spec((), jnp.int32, one_chip)
    ex = LocalPipelineExecutor(cfg, params)
    return {
        "stage": (ex._stage_fn, (params, x, ids, bound, bound)),
        "embed": (ex._embed_fn, (params, ids)),
        "head": (ex._head_fn, (params, x)),
    }


@pytest.mark.parametrize("name", ["stage", "embed", "head"])
def test_qwen3_4b_served_path_fits_one_chip(qwen3_4b, name):
    fn, args = qwen3_4b[name]
    mem = fn.lower(*args).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, (name, total)


def test_flash_attention_compiles(one_chip):
    q = _spec((1, 32, SEQ, 128), jnp.bfloat16, one_chip)
    kv = _spec((1, 8, SEQ, 128), jnp.bfloat16, one_chip)
    c = _compile(lambda q, k, v: ops.flash_attention(q, k, v), q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_decode_attention_compiles(one_chip):
    # Qwen3-4B heads (32 q / 8 kv of 128) over an 8 x 4096 cache.
    q = _spec((8, 32, 128), jnp.bfloat16, one_chip)
    kv = _spec((8, 8, 4096, 128), jnp.bfloat16, one_chip)
    idx = _spec((), jnp.int32, one_chip)
    c = _compile(lambda q, k, v, i: ops.decode_attention(q, k, v, i),
                 q, kv, kv, idx)
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles(one_chip):
    # Mamba2-370m: d_inner 2048 = 32 heads of 64, d_state 128.
    cfg = get_config("mamba2-370m")
    H = cfg.ssm.num_heads(cfg.d_model)
    P, N = cfg.ssm.head_dim, cfg.ssm.d_state
    x = _spec((1, SEQ, H, P), jnp.bfloat16, one_chip)
    dt = _spec((1, SEQ, H), jnp.bfloat16, one_chip)
    A = _spec((H,), jnp.float32, one_chip)
    bc = _spec((1, SEQ, N), jnp.bfloat16, one_chip)
    c = _compile(lambda *a: ops.ssd_scan(*a, chunk=cfg.ssm.chunk_size),
                 x, dt, A, bc, bc)
    assert "tpu_custom_call" in c.as_text()
