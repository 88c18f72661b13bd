"""Plain float32 reference of the Granite-4.0-H hybrid decoder.

Follows ibm-granite/granite-4.0-h-micro ``config.json`` and the
``GraniteMoeHybridForCausalLM`` modelling code: 40 pre-norm layers, each
a mixer and a SwiGLU MLP; the mixer is Mamba-2 or, at the layers
``layer_types`` names ``attention``, grouped-query attention without
position embedding (``position_embedding_type: nope``) whose scores are
scaled by ``attention_multiplier``.  The embedded tokens are multiplied
by ``embedding_multiplier``, each mixer's and each MLP's output by
``residual_multiplier`` before it is added, and the logits divided by
``logits_scaling``.  The Mamba-2 mixer is the one of
``reference/mamba2.py`` (its sequential recurrence; one group, conv
bias, the gated RMSNorm at ``rms_norm_eps``), the attention that of
``reference/qwen3.py``; ``shared_intermediate_size`` is the MLP's width
(``num_local_experts`` is 0).

Departures from the published model, each also in the configuration
file: the LM head is a matrix of its own (``tie_word_embeddings`` is
false), as the program serves it.

Weights use the program's layout for a model whose layers mix kinds:
``embed/table [V, d]``; ``blocks/{ln1,ln2}/scale [layers, d]``;
``blocks/attn/mixer/{wq,wk,wv,wo}`` stacked over the attention layers;
``blocks/mamba/mixer/{wz,wx,wB,wC,wdt,conv_w,conv_b,A_log,D,dt_bias,
norm_scale,out_proj}`` over the Mamba-2 layers (``A_log``, ``D``,
``dt_bias`` in f32); ``blocks/mlp/ffn/{wi (up), wg (gate), wo (down)}``
over all layers; ``final_norm/scale``, ``head/w [d, V]``.  Everything
is computed in f32 at full matmul precision, one layer at a time.
Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import mamba2, qwen3
from .common import F32, draw, fp8, matmul, rms_norm, tree_bytes


def dims(c):
    d, nq = c["hidden_size"], c["num_attention_heads"]
    din = c["mamba_expand"] * d
    kinds = c["layer_types"]
    return dict(d=d, L=c["num_hidden_layers"], nq=nq,
                nkv=c["num_key_value_heads"], hd=c.get("head_dim") or d // nq,
                ff=c["shared_intermediate_size"], V=c["vocab_size"],
                din=din, N=c["mamba_d_state"], K=c["mamba_d_conv"],
                P=c["mamba_d_head"], H=c["mamba_n_heads"],
                nA=kinds.count("attention"), nM=kinds.count("mamba"))


def param_shapes(c, dtype):
    k = dims(c)
    d, L, nq, nkv, hd, ff, V = (k[n] for n in
                                ("d", "L", "nq", "nkv", "hd", "ff", "V"))
    din, N, K, H, nA, nM = (k[n] for n in ("din", "N", "K", "H", "nA", "nM"))

    def s(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt)

    return {
        "embed": {"table": s(V, d)},
        "blocks": {
            "ln1": {"scale": s(L, d)},
            "ln2": {"scale": s(L, d)},
            "attn": {"mixer": {"wq": s(nA, d, nq * hd),
                               "wk": s(nA, d, nkv * hd),
                               "wv": s(nA, d, nkv * hd),
                               "wo": s(nA, nq * hd, d)}},
            "mamba": {"mixer": {
                "wz": s(nM, d, din), "wx": s(nM, d, din), "wB": s(nM, d, N),
                "wC": s(nM, d, N), "wdt": s(nM, d, H),
                "conv_w": s(nM, K, din + 2 * N), "conv_b": s(nM, din + 2 * N),
                "A_log": s(nM, H, dt=F32), "D": s(nM, H, dt=F32),
                "dt_bias": s(nM, H, dt=F32),
                "norm_scale": s(nM, din), "out_proj": s(nM, din, d)}},
            "mlp": {"ffn": {"wi": s(L, d, ff), "wg": s(L, d, ff),
                            "wo": s(L, ff, d)}},
        },
        "final_norm": {"scale": s(d)},
        "head": {"w": s(d, V)},
    }


def init_weights(c, key, dtype):
    """Seeded weights in ``dtype`` (f32 for ``A_log``, ``D``,
    ``dt_bias``), made in one jitted program, by the laws of
    ``reference/mamba2.py``."""
    shapes = param_shapes(c, dtype)

    def make(k):
        w = draw(k, shapes, mamba2._law)
        m = w["blocks"]["mamba"]["mixer"]
        # The laws drew A = exp(A_log) and the step size itself; store
        # their logarithm and the softplus inverse.
        m["A_log"] = jnp.log(m["A_log"])
        m["dt_bias"] = m["dt_bias"] + jnp.log(-jnp.expm1(-m["dt_bias"]))
        return w

    return jax.jit(make)(key)


def make_forward(c, quant: bool = False):
    """``forward(weights, tokens [S]) -> logits [S, V]`` in f32.

    ``quant`` rounds every matmul operand, the convolution's weights and
    the embedding rows to fp8: the control, which the comparison has to
    reject.  The recurrence itself stays f32 in both.
    """
    k = dims(c)
    eps = c["rms_norm_eps"]
    nq, nkv, hd = k["nq"], k["nkv"], k["hd"]
    din, N, H, P = k["din"], k["N"], k["H"], k["P"]
    kinds = c["layer_types"]
    slot = [kinds[:i].count(t) for i, t in enumerate(kinds)]
    res = c["residual_multiplier"]
    # ``qwen3._attention`` scales scores by hd ** -0.5; scaling q first
    # by attention_multiplier * hd ** 0.5 gives attention_multiplier.
    q_scale = c["attention_multiplier"] * hd ** 0.5

    def w(x):
        return x.astype(F32)

    @jax.jit
    def embed(table, tokens):
        x = jnp.take(table, tokens, axis=0).astype(F32)
        return (fp8(x) if quant else x) * c["embedding_multiplier"]

    @jax.jit
    def attention(m, h):
        S = h.shape[0]
        q = matmul("sd,dk->sk", h, w(m["wq"]), quant).reshape(S, nq, hd)
        kk = matmul("sd,dk->sk", h, w(m["wk"]), quant).reshape(S, nkv, hd)
        v = matmul("sd,dk->sk", h, w(m["wv"]), quant).reshape(S, nkv, hd)
        o = qwen3._attention(q * q_scale, kk, v, quant)
        return matmul("sk,kd->sd", o, w(m["wo"]), quant)

    @jax.jit
    def ssm(m, h):
        S = h.shape[0]
        z = matmul("sd,dk->sk", h, w(m["wz"]), quant)
        xs = matmul("sd,dk->sk", h, w(m["wx"]), quant)
        bm = matmul("sd,dn->sn", h, w(m["wB"]), quant)
        cm = matmul("sd,dn->sn", h, w(m["wC"]), quant)
        dt = matmul("sd,dh->sh", h, w(m["wdt"]), quant)
        conv_w = fp8(w(m["conv_w"])) if quant else w(m["conv_w"])
        xbc = jax.nn.silu(mamba2._conv(jnp.concatenate([xs, bm, cm], -1),
                                       conv_w, w(m["conv_b"])))
        xs, bm, cm = xbc[:, :din], xbc[:, din:din + N], xbc[:, din + N:]
        dt = jax.nn.softplus(dt + m["dt_bias"])
        A = -jnp.exp(m["A_log"])
        y = mamba2._ssm(xs.reshape(S, H, P), dt, A, bm, cm, m["D"])
        y = rms_norm(y.reshape(S, din) * jax.nn.silu(z), m["norm_scale"],
                     eps)
        return matmul("sk,kd->sd", y, w(m["out_proj"]), quant)

    @jax.jit
    def mlp(blocks, i, x):
        f = jax.tree.map(lambda a: a[i], blocks["mlp"]["ffn"])
        h = rms_norm(x, blocks["ln2"]["scale"][i], eps)
        g = matmul("sd,df->sf", h, w(f["wg"]), quant)
        u = matmul("sd,df->sf", h, w(f["wi"]), quant)
        return x + res * matmul("sf,fd->sd", jax.nn.silu(g) * u,
                                w(f["wo"]), quant)

    @jax.jit
    def head(params, x):
        x = rms_norm(x, params["final_norm"]["scale"], eps)
        return matmul("sd,dv->sv", x, w(params["head"]["w"]),
                      quant) / c["logits_scaling"]

    def forward(weights, tokens):
        with jax.default_matmul_precision("highest"):
            b = weights["blocks"]
            x = embed(weights["embed"]["table"], tokens)
            for i, kind in enumerate(kinds):
                h = rms_norm(x, b["ln1"]["scale"][i], eps)
                if kind == "attention":
                    m = jax.tree.map(lambda a: a[slot[i]], b["attn"]["mixer"])
                    x = x + res * attention(m, h)
                else:
                    m = jax.tree.map(lambda a: a[slot[i]],
                                     b["mamba"]["mixer"])
                    x = x + res * ssm(m, h)
                x = mlp(b, i, x)
            return head({"final_norm": weights["final_norm"],
                         "head": weights["head"]}, x)

    return forward


def costs(c, seq: int, dtype):
    """Least work of one query of ``seq`` tokens, from shapes, per layer.

    ``layer_flops[i]``: the matmuls of layer ``i`` (2 per multiply-add):
    its MLP, and either the attention projections plus causal attention
    (``S(S+1)/2`` query-key pairs for the scores and as many for the
    weighted values) or the Mamba-2 projections, convolution and SSD
    terms counted as the recurrence needs them (per token and head a
    ``P x N`` state decayed and updated, 2 P N, and read against ``C``,
    2 P N; the chunked form the program uses does more).
    ``layer_bytes[i]``: the layer's weights, each read once.
    ``act_bytes``: one ``[S, d]`` activation.  Norms, gates and softmax
    are elementwise and counted in neither.
    """
    k = dims(c)
    d, nq, nkv, hd, ff, V = (k[n] for n in ("d", "nq", "nkv", "hd", "ff", "V"))
    din, N, K, H, P = (k[n] for n in ("din", "N", "K", "H", "P"))
    S = seq
    mlp = 2 * S * d * ff * 3
    attn = (2 * S * d * (nq * hd + 2 * nkv * hd) + 2 * S * nq * hd * d
            + 2 * 2 * nq * hd * S * (S + 1) // 2)
    ssd = (2 * S * d * (2 * din + 2 * N + H) + 2 * S * din * d
           + 2 * S * K * (din + 2 * N) + 4 * S * H * P * N)
    b = param_shapes(c, dtype)["blocks"]
    norms = tree_bytes([b["ln1"], b["ln2"], b["mlp"]]) // k["L"]
    mixer = {"attention": tree_bytes(b["attn"]) // k["nA"],
             "mamba": tree_bytes(b["mamba"]) // k["nM"]}
    kinds = c["layer_types"]
    return {
        "layer_flops": [mlp + (attn if t == "attention" else ssd)
                        for t in kinds],
        "layer_bytes": [norms + mixer[t] for t in kinds],
        "act_bytes": S * d * jnp.dtype(dtype).itemsize,
        "head_flops": 2 * S * d * V,
        "num_blocks": k["L"],
    }
