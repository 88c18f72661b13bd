"""Pallas kernel validation: interpret-mode sweeps vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional-dep shim

from repro.kernels import ops
from repro.kernels import ref as R

KEY = jax.random.PRNGKey(0)


def _rand(shape, k, dtype):
    x = jax.random.normal(k, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Hq, Hkv, S, D, causal, window, dtype
    (2, 4, 2, 256, 64, True, None, jnp.float32),
    (1, 8, 8, 128, 128, True, None, jnp.float32),   # MHA
    (2, 4, 1, 256, 64, False, None, jnp.float32),   # encoder + MQA
    (1, 4, 2, 512, 64, True, 128, jnp.float32),     # sliding window
    (1, 4, 2, 256, 80, True, None, jnp.float32),    # hubert head dim
    (1, 2, 2, 128, 56, True, None, jnp.float32),    # qwen2 head dim
    (2, 4, 2, 256, 64, True, None, jnp.bfloat16),
    (1, 4, 2, 512, 128, True, 256, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D,causal,window,dtype", FLASH_CASES,
    ids=[f"B{c[0]}Hq{c[1]}Hkv{c[2]}S{c[3]}D{c[4]}c{int(c[5])}"
         f"w{c[6]}{jnp.dtype(c[7]).name}" for c in FLASH_CASES])
def test_flash_attention(B, Hq, Hkv, S, D, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand((B, Hq, S, D), ks[0], dtype)
    k = _rand((B, Hkv, S, D), ks[1], dtype)
    v = _rand((B, Hkv, S, D), ks[2], dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="interpret", block_q=64, block_k=64)
    refo = R.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(refo, np.float32), **_tol(dtype))


@given(bq=st.sampled_from([32, 64, 128]), bk=st.sampled_from([32, 64, 128]))
@settings(max_examples=6, deadline=None)
def test_flash_block_shape_independence(bq, bk):
    """Output must not depend on the BlockSpec tiling."""
    ks = jax.random.split(KEY, 3)
    q = _rand((1, 2, 256, 64), ks[0], jnp.float32)
    k = _rand((1, 2, 256, 64), ks[1], jnp.float32)
    v = _rand((1, 2, 256, 64), ks[2], jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, impl="interpret",
                              block_q=bq, block_k=bk)
    refo = R.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(refo), atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DECODE_CASES = [
    (2, 8, 2, 512, 64, 300, None, jnp.float32),
    (1, 4, 4, 256, 128, 17, None, jnp.float32),
    (2, 8, 2, 512, 64, 400, 128, jnp.float32),      # sliding window
    (1, 14, 2, 256, 64, 255, None, jnp.float32),    # qwen2 ratios
    (2, 8, 2, 512, 64, 300, None, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D,idx,window,dtype", DECODE_CASES,
    ids=[f"B{c[0]}Hq{c[1]}S{c[3]}i{c[5]}w{c[6]}{jnp.dtype(c[7]).name}"
         for c in DECODE_CASES])
def test_decode_attention(B, Hq, Hkv, S, D, idx, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand((B, Hq, D), ks[0], dtype)
    k = _rand((B, Hkv, S, D), ks[1], dtype)
    v = _rand((B, Hkv, S, D), ks[2], dtype)
    out = ops.decode_attention(q, k, v, jnp.int32(idx), window=window,
                               impl="interpret", block_k=128)
    refo = R.decode_attention_ref(q, k, v, idx, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(refo, np.float32), **_tol(dtype))


def test_decode_ignores_stale_cache_beyond_index():
    """Slots past `index` must not leak into the output."""
    ks = jax.random.split(KEY, 3)
    q = _rand((1, 4, 2, 64)[0:3] + (64,), ks[0], jnp.float32)
    q = _rand((1, 4, 64), ks[0], jnp.float32)
    k = _rand((1, 2, 256, 64), ks[1], jnp.float32)
    v = _rand((1, 2, 256, 64), ks[2], jnp.float32)
    out1 = ops.decode_attention(q, k, v, jnp.int32(100), impl="interpret",
                                block_k=64)
    k2 = k.at[:, :, 101:].set(99.0)
    v2 = v.at[:, :, 101:].set(-99.0)
    out2 = ops.decode_attention(q, k2, v2, jnp.int32(100), impl="interpret",
                                block_k=64)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    (2, 256, 8, 64, 32, 64, 4, jnp.float32),
    (1, 128, 4, 32, 64, 32, 4, jnp.float32),
    (1, 256, 16, 64, 128, 64, 8, jnp.float32),      # mamba2-370m dims
    (2, 128, 8, 64, 32, 32, 8, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "b,S,H,P,N,chunk,bh,dtype", SSD_CASES,
    ids=[f"b{c[0]}S{c[1]}H{c[2]}P{c[3]}N{c[4]}{jnp.dtype(c[7]).name}"
         for c in SSD_CASES])
def test_ssd_scan(b, S, H, P, N, chunk, bh, dtype):
    ks = jax.random.split(KEY, 5)
    x = _rand((b, S, H, P), ks[0], dtype)
    dt = jax.nn.softplus(_rand((b, S, H), ks[1], jnp.float32)).astype(dtype)
    A = -jnp.exp(_rand((H,), ks[2], jnp.float32) * 0.5)
    B_ = _rand((b, S, N), ks[3], dtype)
    C = _rand((b, S, N), ks[4], dtype)
    out = ops.ssd_scan(x, dt, A.astype(dtype), B_, C, chunk=chunk,
                       block_h=bh, impl="interpret")
    refo = R.ssd_scan_ref(x, dt, A, B_, C)
    scale = float(np.max(np.abs(np.asarray(refo, np.float32)))) + 1e-9
    err = np.max(np.abs(np.asarray(out, np.float32)
                        - np.asarray(refo, np.float32))) / scale
    assert err < (5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_matches_model_chunked_form():
    """Kernel == models.mamba2.ssd_chunked == naive recurrence."""
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(KEY, 5)
    b, S, H, P, N = 2, 256, 8, 64, 32
    x = _rand((b, S, H, P), ks[0], jnp.float32)
    dt = jax.nn.softplus(_rand((b, S, H), ks[1], jnp.float32))
    A = -jnp.exp(_rand((H,), ks[2], jnp.float32) * 0.5)
    B_ = _rand((b, S, N), ks[3], jnp.float32)
    C = _rand((b, S, N), ks[4], jnp.float32)
    y_kernel = ops.ssd_scan(x, dt, A, B_, C, chunk=64, block_h=4,
                            impl="interpret")
    y_model, _ = ssd_chunked(x, dt, A, B_, C, chunk=64)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               atol=5e-4, rtol=1e-4)


@pytest.mark.parametrize("op,nargs", [("flash_attention", 3),
                                      ("decode_attention", 4),
                                      ("ssd_scan", 5)])
def test_unknown_impl_is_refused(op, nargs):
    """No impl is picked from the backend: a name outside ops.IMPLS
    ("auto" among them) raises instead of falling back."""
    args = [jnp.zeros((1, 1, 8, 8))] * nargs
    with pytest.raises(ValueError, match="impl="):
        getattr(ops, op)(*args, impl="auto")
