"""Pallas TPU Mamba2 SSD chunk scan.

The SSD dual form maps naturally onto the MXU: within a chunk the token
mixing is three dense contractions ((C·Bᵀ)∘L against x, plus the state
read/write terms); across chunks a [H, N, P] state is carried — here it
lives in VMEM scratch across the innermost (sequential) chunk grid axis,
so the recurrence never round-trips HBM.

Grid = (B, H/block_h, nc).  Head-blocking bounds the VMEM working set:
state tile is block_h × N × P fp32 (e.g. 8×128×64×4 = 256 KiB for Jamba's
d_inner = 16384 where a full-head state would be 8 MiB).

Mosaic lowers no ``cumsum`` and no 3-D contraction, so the kernel body is
2-D throughout: cumulative sums are matmuls against a lower-triangular
ones matrix (at ``HIGHEST`` precision, since they sum log-decays), and
the heads of a block are unrolled.  ``B`` enters transposed ([b, N, S])
so every contraction is a plain or rhs-transposed matmul.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, *, rhs_t: bool = False, precision=None):
    """``a @ b`` (or ``a @ bᵀ`` with ``rhs_t``), accumulated in fp32."""
    dims = (((1,), (1 if rhs_t else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dt_ref, a_ref, bt_ref, c_ref, y_ref, state_scr):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    bh, cs = dt_ref.shape[1], dt_ref.shape[2]
    dt = dt_ref[0].astype(jnp.float32)        # [bh, cs]
    A = a_ref[0].astype(jnp.float32)          # [bh, 1]
    Bt = bt_ref[0].astype(jnp.float32)        # [N, cs]
    Cc = c_ref[0].astype(jnp.float32)         # [cs, N]

    i = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    causal = i >= j
    tri = causal.astype(jnp.float32)          # tri[i, j] = [j <= i]

    dA = dt * A                               # [bh, cs]
    dA_cs = _dot(dA, tri, rhs_t=True, precision=_HI)   # [bh, cs] cumsum
    cb = _dot(Cc, Bt)                         # [cs, cs]

    for h in range(bh):
        x = x_ref[0, h].astype(jnp.float32)   # [cs, P]
        xdt = x * dt[h:h + 1].reshape(cs, 1)  # [cs, P]
        row = dA_cs[h:h + 1]                  # [1, cs]
        col = _dot(tri, dA[h:h + 1], rhs_t=True, precision=_HI)  # [cs, 1]
        last = row[:, cs - 1:]                # [1, 1]

        # Intra-chunk (dual quadratic form): (C·Bᵀ ∘ L) @ (x·dt)
        L = jnp.exp(jnp.where(causal, col - row, -jnp.inf))   # [cs, cs]
        y = _dot(cb * L, xdt)

        # State read (inter-chunk): y += (C · h_prev) with decay
        state = state_scr[h]                  # [N, P]
        y = y + _dot(Cc, state) * jnp.exp(col)

        # State write: h = h * exp(sum dA) + Bᵀ·(decay ⊙ x·dt)
        decay = jnp.exp(last - col)           # [cs, 1]
        # exp(sum dA) as a [1, P] row (a matmul with ones): Mosaic cannot
        # broadcast a [1, 1] value across sublanes and lanes at once.
        total = _dot(dA[h:h + 1], jnp.ones(x.shape, jnp.float32),
                     precision=_HI)
        state_scr[h] = state * jnp.exp(total) + _dot(Bt, xdt * decay)

        y_ref[0, h] = y.astype(y_ref.dtype)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, *,
             chunk: int = 256, block_h: int = 8,
             interpret: bool = False) -> jnp.ndarray:
    """SSD scan (layout matches repro.models.mamba2.ssd_chunked).

    x: [b, S, H, P]; dt: [b, S, H]; A: [H]; B, C: [b, S, N].
    Returns y: [b, S, H, P].
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    block_h = min(block_h, H)
    if H % block_h:
        raise ValueError(f"H={H} not divisible by block_h={block_h}")
    nc = S // chunk
    nh = H // block_h

    # Layout: heads-major so a head-block×chunk tile is contiguous.
    xt = x.transpose(0, 2, 1, 3)              # [b, H, S, P]
    dtt = dt.transpose(0, 2, 1)               # [b, H, S]
    Bt = B.transpose(0, 2, 1)                 # [b, N, S]
    # A as [nh, bh, 1]: a head block's trailing dims equal the array's, so
    # Mosaic accepts the tile (a rank-1 [bh] block must be whole or a
    # multiple of 128).
    At = A.reshape(nh, block_h, 1)

    yt = pl.pallas_call(
        _ssd_kernel,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, block_h, chunk, P),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, block_h, chunk),
                         lambda bi, hi, ci: (bi, hi, ci)),
            pl.BlockSpec((1, block_h, 1), lambda bi, hi, ci: (hi, 0, 0)),
            pl.BlockSpec((1, N, chunk), lambda bi, hi, ci: (bi, 0, ci)),
            pl.BlockSpec((1, chunk, N), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_h, chunk, P),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_h, N, P), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, At, Bt, C)
    return yt.transpose(0, 2, 1, 3)
