"""Mamba2 block: SSD (state-space duality) chunked forward + recurrent decode.

Follows the discrete SSD formulation of arXiv:2405.21060 (minimal
reference): within a chunk the token mixing is the quadratic dual form
(attention-like, MXU-friendly); across chunks a linear state recurrence
carries [H, P, N] states.  ``n_groups = 1`` (B/C shared across heads).

The chunked scan here is the pure-jnp reference; the Pallas kernel in
``repro.kernels.ssd_scan`` implements the same contraction with explicit
VMEM tiling and is validated against :func:`ssd_chunked`.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import rms_norm


# ---------------------------------------------------------------------------
# Core SSD math
# ---------------------------------------------------------------------------


def segsum(x: jnp.ndarray) -> jnp.ndarray:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} x[..., k]
    (lower-triangular; -inf above the diagonal)."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                B: jnp.ndarray, C: jnp.ndarray,
                chunk: int = 256,
                init_state: jnp.ndarray | None = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD scan.

    x:  [b, S, H, P]   (already multiplied by nothing; dt applied inside)
    dt: [b, S, H]      (post-softplus, > 0)
    A:  [H]            (negative)
    B:  [b, S, N], C: [b, S, N]  (n_groups=1, shared across heads)
    Returns (y [b, S, H, P], final_state [b, H, P, N]).

    The decays (``dt * A``, its cumulative sum over the chunk, the
    segment sums and their ``exp``) are computed in f32 whatever the
    inputs' dtype: within a 256-token chunk the sum reaches about -100,
    where one bf16 ulp is 0.5.  Only the decay factors that enter the
    einsums are cast to ``x.dtype``.
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    if S % chunk:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    nc = S // chunk

    xb = x.reshape(b, nc, chunk, H, P)
    dtb = dt.reshape(b, nc, chunk, H)
    Bb = B.reshape(b, nc, chunk, N)
    Cb = C.reshape(b, nc, chunk, N)

    f32 = jnp.float32
    dA = dtb.astype(f32) * A.astype(f32)[None, None, None, :]  # [b,nc,cs,H]
    dA = jnp.moveaxis(dA, -1, -2)                      # [b,nc,H,cs]
    dA_cs = jnp.cumsum(dA, axis=-1)                    # [b,nc,H,cs]

    # 1. Intra-chunk (diagonal block) output: quadratic dual form.
    L = jnp.exp(segsum(dA)).astype(x.dtype)            # [b,nc,H,cs,cs]
    # scores: C_i · B_j
    cb = jnp.einsum("bcin,bcjn->bcij", Cb, Bb)         # [b,nc,cs,cs]
    xdt = xb * dtb.astype(x.dtype)[..., None]          # [b,nc,cs,H,P]
    y_diag = jnp.einsum("bcij,bchij,bcjhp->bcihp",
                        cb, L, xdt)

    # 2. Chunk states: decayed sum of B ⊗ x within each chunk.
    decay_states = jnp.exp(dA_cs[..., -1:] - dA_cs).astype(x.dtype)
    states = jnp.einsum("bchl,bcln,bclhp->bchpn",
                        decay_states, Bb, xdt)         # [b,nc,H,P,N]

    # 3. Inter-chunk recurrence.
    chunk_decay = jnp.exp(dA_cs[..., -1])              # [b,nc,H]
    s0 = (init_state if init_state is not None
          else jnp.zeros((b, H, P, N), x.dtype))

    def scan_fn(carry, inp):
        st, dec = inp                                  # [b,H,P,N], [b,H]
        prev = carry
        new = prev * dec[..., None, None] + st
        return new, prev

    (final_state, prev_states) = jax.lax.scan(
        scan_fn, s0.astype(f32),
        (jnp.moveaxis(states, 1, 0).astype(f32),
         jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)      # [b,nc,H,P,N]

    # 4. Inter-chunk (off-diagonal) output: read previous state.
    state_decay = jnp.exp(dA_cs).astype(x.dtype)       # [b,nc,H,cs]
    y_off = jnp.einsum("bcln,bchpn,bchl->bclhp",
                       Cb, prev_states.astype(x.dtype), state_decay)

    y = (y_diag + y_off).reshape(b, S, H, P)
    return y, final_state.astype(x.dtype)


def ssd_step(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray,
             state: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token recurrence.

    x: [b, H, P]; dt: [b, H]; B, C: [b, N]; state: [b, H, P, N].
    h' = h * exp(dt A) + dt * x ⊗ B ;  y = h' · C  (the decay in f32)
    """
    f32 = jnp.float32
    dA = jnp.exp(dt.astype(f32) * A.astype(f32)[None, :])    # [b,H]
    xdt = x * dt.astype(x.dtype)[..., None]            # [b,H,P]
    new_state = (state * dA.astype(state.dtype)[..., None, None]
                 + jnp.einsum("bhp,bn->bhpn", xdt, B))
    y = jnp.einsum("bhpn,bn->bhp", new_state, C)
    return y, new_state


# ---------------------------------------------------------------------------
# Full Mamba2 block (projections + conv + SSD + gated norm)
# ---------------------------------------------------------------------------


def init_mamba(rng, cfg: ModelConfig, dtype=jnp.float32):
    """Projections are kept *separate* (wz/wx/wB/wC/wdt) rather than fused
    into one in_proj: a fused projection's z/xBC/dt slice boundaries do
    not align with tensor-parallel shard boundaries, which forces XLA to
    re-gather the SSM state at every sublayer (observed 3.9 GiB/chip on
    the Jamba decode step, §Perf iteration 6).  Separate matmuls have
    identical FLOPs and shard cleanly: wz/wx/wdt on heads/channels, the
    small wB/wC (and their convs) replicated."""
    s = cfg.ssm
    d = cfg.d_model
    din = s.d_inner(d)
    H = s.num_heads(d)
    N = s.d_state
    ks = jax.random.split(rng, 7)
    sc = d ** -0.5
    return {
        "wz": (jax.random.normal(ks[0], (d, din)) * sc).astype(dtype),
        "wx": (jax.random.normal(ks[1], (d, din)) * sc).astype(dtype),
        "wB": (jax.random.normal(ks[2], (d, N)) * sc).astype(dtype),
        "wC": (jax.random.normal(ks[3], (d, N)) * sc).astype(dtype),
        "wdt": (jax.random.normal(ks[4], (d, H)) * sc).astype(dtype),
        "conv_w": (jax.random.normal(ks[5], (s.d_conv, din + 2 * N))
                   * (s.d_conv * (din + 2 * N)) ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((din + 2 * N,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm_scale": jnp.ones((din,), dtype),
        "out_proj": (jax.random.normal(ks[6], (din, d)) * din ** -0.5
                     ).astype(dtype),
    }


def _project(params, x: jnp.ndarray):
    """x: [B, S, d] -> z, xBC (pre-conv), dt."""
    z = jnp.einsum("bsd,dk->bsk", x, params["wz"])
    xs = jnp.einsum("bsd,dk->bsk", x, params["wx"])
    Bm = jnp.einsum("bsd,dn->bsn", x, params["wB"])
    Cm = jnp.einsum("bsd,dn->bsn", x, params["wC"])
    dt = jnp.einsum("bsd,dh->bsh", x, params["wdt"])
    xBC = jnp.concatenate([xs, Bm, Cm], axis=-1)
    return z, xBC, dt


def _causal_conv(xBC: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 init: jnp.ndarray | None = None) -> jnp.ndarray:
    """Depthwise causal conv1d.  xBC: [B, S, Cd]; w: [K, Cd].

    ``init``: [B, K-1, Cd] left-context (decode prefill continuity)."""
    K = w.shape[0]
    if init is None:
        pad = jnp.zeros((xBC.shape[0], K - 1, xBC.shape[-1]), xBC.dtype)
    else:
        pad = init.astype(xBC.dtype)
    xp = jnp.concatenate([pad, xBC], axis=1)          # [B, S+K-1, Cd]
    out = sum(xp[:, i:i + xBC.shape[1]] * w[i] for i in range(K))
    return out + b


def mamba_forward(params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Train/prefill forward.  x: [B, S, d] -> [B, S, d]."""
    s = cfg.ssm
    B_, S, d = x.shape
    din = s.d_inner(d)
    N = s.d_state
    H = s.num_heads(d)
    P = s.head_dim

    z, xBC, dt = _project(params, x)
    xBC = jax.nn.silu(_causal_conv(xBC, params["conv_w"], params["conv_b"]))
    xs = xBC[..., :din].reshape(B_, S, H, P)
    Bm = xBC[..., din:din + N]
    Cm = xBC[..., din + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    chunk = min(s.chunk_size, S)
    while S % chunk:
        chunk //= 2
    y, _ = ssd_chunked(xs, dt, A, Bm, Cm, chunk=chunk)
    y = y + xs * params["D"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(B_, S, din)
    y = rms_norm(y * jax.nn.silu(z), params["norm_scale"], cfg.rms_eps)
    return jnp.einsum("bsk,kd->bsd", y, params["out_proj"])


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype):
    s = cfg.ssm
    din = s.d_inner(cfg.d_model)
    H = s.num_heads(cfg.d_model)
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, din + 2 * s.d_state), dtype),
        "ssm": jnp.zeros((batch, H, s.head_dim, s.d_state), dtype),
    }


def mamba_decode(params, cfg: ModelConfig, x: jnp.ndarray, cache: dict):
    """x: [B, 1, d] -> ([B, 1, d], updated cache)."""
    s = cfg.ssm
    B_, _, d = x.shape
    din = s.d_inner(d)
    N = s.d_state
    H = s.num_heads(d)
    P = s.head_dim

    z, xBC, dt = _project(params, x)
    # conv over the cached window + current token
    window = jnp.concatenate([cache["conv"], xBC], axis=1)  # [B, K, Cd]
    conv_out = (jnp.einsum("bkc,kc->bc", window, params["conv_w"])
                + params["conv_b"])[:, None, :]
    xBC = jax.nn.silu(conv_out)
    new_conv = window[:, 1:]

    xs = xBC[..., :din].reshape(B_, H, P)
    Bm = xBC[:, 0, din:din + N]
    Cm = xBC[:, 0, din + N:]
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    y, new_ssm = ssd_step(xs, dtv, A, Bm, Cm, cache["ssm"])
    y = y + xs * params["D"].astype(x.dtype)[None, :, None]
    y = y.reshape(B_, 1, din)
    y = rms_norm(y * jax.nn.silu(z), params["norm_scale"], cfg.rms_eps)
    out = jnp.einsum("bsk,kd->bsd", y, params["out_proj"])
    return out, {"conv": new_conv, "ssm": new_ssm.astype(cache["ssm"].dtype)}
