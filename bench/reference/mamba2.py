"""Plain float32 reference of the Mamba-2 language model.

Follows the Mamba-2 description (arXiv:2405.21060, the ``Mamba2``
mixer of state-spaces/mamba): pre-norm residual blocks, each one mixer
and no MLP.  The mixer projects ``x`` to ``z``, ``x``, ``B``, ``C``
and ``dt``; a causal depthwise convolution of width ``d_conv`` with a
bias, then SiLU, runs over ``concat(x, B, C)``;
``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` per head; the
state-space model is computed as its sequential recurrence

    h_t = exp(dt_t A) h_t-1 + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

with one group (``B`` and ``C`` shared by all heads); then the gated
RMSNorm ``norm(y * silu(z))`` and the output projection.  A final
RMSNorm and the LM head close the model.

Departures from the published model, each also in the configuration
file: the LM head is a matrix of its own (the published model ties it
to the embedding); the input projection is kept as five matrices
(``wz, wx, wB, wC, wdt``), which is the fused ``in_proj`` split by its
rows; the RMSNorm epsilon is the configuration's ``norm_epsilon``.

Weights use the program's stacked layout ``[layers, ...]``:
``blocks/sub0/ln1/scale`` and ``blocks/sub0/mixer/{wz, wx, wB, wC, wdt
[d, *], conv_w [d_conv, d_inner + 2 d_state], conv_b, A_log, D,
dt_bias [heads], norm_scale [d_inner], out_proj [d_inner, d]}``, plus
``embed/table``, ``final_norm/scale`` and ``head/w``.  ``A_log``, ``D``
and ``dt_bias`` are kept in f32, as the program keeps them.  Nothing
here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, draw, fp8, matmul, rms_norm, tree_bytes


def dims(c):
    d = c["d_model"]
    din = c["expand"] * d
    return dict(d=d, L=c["n_layer"], din=din, N=c["d_state"],
                K=c["d_conv"], P=c["headdim"], H=din // c["headdim"],
                V=c["vocab_size"])


def param_shapes(c, dtype):
    k = dims(c)
    d, L, din, N, K, H, V = (k[n] for n in
                             ("d", "L", "din", "N", "K", "H", "V"))

    def s(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt)

    return {
        "embed": {"table": s(V, d)},
        "blocks": {"sub0": {
            "ln1": {"scale": s(L, d)},
            "mixer": {
                "wz": s(L, d, din), "wx": s(L, d, din), "wB": s(L, d, N),
                "wC": s(L, d, N), "wdt": s(L, d, H),
                "conv_w": s(L, K, din + 2 * N), "conv_b": s(L, din + 2 * N),
                "A_log": s(L, H, dt=F32), "D": s(L, H, dt=F32),
                "dt_bias": s(L, H, dt=F32),
                "norm_scale": s(L, din), "out_proj": s(L, din, d)},
        }},
        "final_norm": {"scale": s(d)},
        "head": {"w": s(d, V)},
    }


def _law(name, shape):
    leaf = name.rsplit("/", 1)[-1]
    if leaf in ("scale", "norm_scale", "D"):
        return ("near_one", 0.1)
    if leaf == "A_log":                      # A = -exp(A_log) in [-16, -1]
        return ("log_uniform", 1.0, 16.0)
    if leaf == "dt_bias":
        return ("log_uniform", 1e-3, 1e-1)
    if leaf == "conv_w":
        return ("normal", shape[-2] ** -0.5)
    if leaf == "conv_b":
        return ("normal", 0.1)
    fan_in = shape[-1] if name == "embed/table" else shape[-2]
    return ("normal", fan_in ** -0.5)


def init_weights(c, key, dtype):
    """Seeded weights in ``dtype`` (f32 for ``A_log``, ``D``,
    ``dt_bias``), made in one jitted program."""
    shapes = param_shapes(c, dtype)

    def make(k):
        w = draw(k, shapes, _law)
        m = w["blocks"]["sub0"]["mixer"]
        # The laws above drew A = exp(A_log) and the step size itself;
        # store their logarithm and the softplus inverse.
        m["A_log"] = jnp.log(m["A_log"])
        m["dt_bias"] = m["dt_bias"] + jnp.log(-jnp.expm1(-m["dt_bias"]))
        return w

    return jax.jit(make)(key)


def _conv(xbc, w, b):
    """Causal depthwise convolution: ``out[t] = b + sum_i w[i] *
    x[t - (K-1) + i]`` with zeros before the sequence."""
    K = w.shape[0]
    S = xbc.shape[0]
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    return b + sum(xp[i:i + S] * w[i] for i in range(K))


def _ssm(x, dt, A, B, C, D):
    """The recurrence over ``t``; x ``[S, H, P]``, dt ``[S, H]``,
    B/C ``[S, N]``; returns ``y [S, H, P]``."""
    H, P = x.shape[1:]
    N = B.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (h * jnp.exp(dtt * A)[:, None, None]
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        y = jnp.einsum("hpn,n->hp", h, ct,
                       precision=jax.lax.Precision.HIGHEST)
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, dt, B, C))
    return y + D[None, :, None] * x


def make_forward(c, quant: bool = False):
    """``forward(weights, tokens [S]) -> logits [S, V]`` in f32.

    ``quant`` rounds every matmul operand and the embedding rows to
    fp8: the control, which the comparison has to reject.  The
    recurrence itself stays f32 in both.
    """
    k = dims(c)
    eps = c["norm_epsilon"]
    din, N, H, P = k["din"], k["N"], k["H"], k["P"]

    def w(x):
        return x.astype(F32)

    @jax.jit
    def embed(table, tokens):
        x = jnp.take(table, tokens, axis=0).astype(F32)
        return fp8(x) if quant else x

    @jax.jit
    def layer(blocks, i, x):
        p = jax.tree.map(lambda a: a[i], blocks["sub0"])
        m = p["mixer"]
        S = x.shape[0]
        h = rms_norm(x, p["ln1"]["scale"], eps)
        z = matmul("sd,dk->sk", h, w(m["wz"]), quant)
        xs = matmul("sd,dk->sk", h, w(m["wx"]), quant)
        bm = matmul("sd,dn->sn", h, w(m["wB"]), quant)
        cm = matmul("sd,dn->sn", h, w(m["wC"]), quant)
        dt = matmul("sd,dh->sh", h, w(m["wdt"]), quant)
        conv_w = fp8(w(m["conv_w"])) if quant else w(m["conv_w"])
        xbc = jax.nn.silu(_conv(jnp.concatenate([xs, bm, cm], -1), conv_w,
                                w(m["conv_b"])))
        xs, bm, cm = xbc[:, :din], xbc[:, din:din + N], xbc[:, din + N:]
        dt = jax.nn.softplus(dt + m["dt_bias"])
        A = -jnp.exp(m["A_log"])
        y = _ssm(xs.reshape(S, H, P), dt, A, bm, cm, m["D"])
        y = rms_norm(y.reshape(S, din) * jax.nn.silu(z), m["norm_scale"],
                     eps)
        return x + matmul("sk,kd->sd", y, w(m["out_proj"]), quant)

    @jax.jit
    def head(params, x):
        x = rms_norm(x, params["final_norm"]["scale"], eps)
        return matmul("sd,dv->sv", x, w(params["head"]["w"]), quant)

    def forward(weights, tokens):
        x = embed(weights["embed"]["table"], tokens)
        for i in range(k["L"]):
            x = layer(weights["blocks"], i, x)
        return head({"final_norm": weights["final_norm"],
                     "head": weights["head"]}, x)

    return forward


def costs(c, seq: int, dtype):
    """Least work of one query of ``seq`` tokens, from shapes.

    ``block_flops``: the projections (2 per multiply-add), the
    convolution, and the state-space model as its recurrence: per token
    and head a ``P x N`` state decayed and updated (2 P N) and read
    against ``C`` (2 P N).  The chunked form the program may use does
    more; what is counted is what the sequence needs.
    ``block_bytes``: the block's weights, each read once.
    ``act_bytes``: one ``[S, d]`` activation.
    """
    k = dims(c)
    d, din, N, K, H, P, V, L = (k[n] for n in
                                ("d", "din", "N", "K", "H", "P", "V", "L"))
    S = seq
    proj = 2 * S * d * (2 * din + 2 * N + H) + 2 * S * din * d
    conv = 2 * S * K * (din + 2 * N)
    ssm = 4 * S * H * P * N
    shapes = param_shapes(c, dtype)
    per_block = tree_bytes(shapes["blocks"]) // L
    return {
        "block_flops": proj + conv + ssm,
        "block_bytes": per_block,
        "act_bytes": S * d * jnp.dtype(dtype).itemsize,
        "head_flops": 2 * S * d * V,
        "num_blocks": L,
    }
