"""Plain float32 reference of the Qwen3 dense decoder.

Follows the Qwen3 description (Qwen/Qwen3-4B ``config.json`` and the
``Qwen3ForCausalLM`` modelling code): pre-norm blocks of grouped-query
attention with a per-head RMSNorm on q and k before rotary embedding
(rotate-half convention, ``rope_theta``), and a SwiGLU MLP
``down(silu(gate(x)) * up(x))``; a final RMSNorm and the LM head.
No bias anywhere.

Departures from the published model, each also in the configuration
file: the LM head is a matrix of its own (``tie_word_embeddings`` is
false), as the program serves it.

Weights use the program's stacked layout, ``[layers, ...]`` per leaf:
``embed/table [V, d]``, ``blocks/sub0/{ln1,ln2}/scale``,
``blocks/sub0/mixer/{wq,wk,wv,wo,q_norm,k_norm}``,
``blocks/sub0/ffn/{wi (up), wg (gate), wo (down)}``,
``final_norm/scale``, ``head/w [d, V]``.  The reference reads them as
given and computes everything in f32, one layer at a time, so that
it fits beside the bf16 weights.  Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, draw, fp8, matmul, rms_norm, tree_bytes

#: Query rows per attention block: keeps the f32 score tensor at
#: ``heads x QBLOCK x S``.
QBLOCK = 256


def dims(c):
    return dict(d=c["hidden_size"], L=c["num_hidden_layers"],
                nq=c["num_attention_heads"], nkv=c["num_key_value_heads"],
                hd=c["head_dim"], ff=c["intermediate_size"],
                V=c["vocab_size"])


def param_shapes(c, dtype):
    k = dims(c)
    d, L, nq, nkv, hd, ff, V = (k[n] for n in
                                ("d", "L", "nq", "nkv", "hd", "ff", "V"))

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    return {
        "embed": {"table": s(V, d)},
        "blocks": {"sub0": {
            "ln1": {"scale": s(L, d)},
            "mixer": {"wq": s(L, d, nq * hd), "wk": s(L, d, nkv * hd),
                      "wv": s(L, d, nkv * hd), "wo": s(L, nq * hd, d),
                      "q_norm": s(L, hd), "k_norm": s(L, hd)},
            "ln2": {"scale": s(L, d)},
            "ffn": {"wi": s(L, d, ff), "wg": s(L, d, ff),
                    "wo": s(L, ff, d)},
        }},
        "final_norm": {"scale": s(d)},
        "head": {"w": s(d, V)},
    }


def _law(name, shape):
    if name.endswith("scale") or name.endswith("_norm"):
        return ("near_one", 0.1)
    fan_in = shape[-2]                 # [.., in, out] matrices
    if name == "embed/table":
        fan_in = shape[-1]
    return ("normal", fan_in ** -0.5)


def init_weights(c, key, dtype):
    """Seeded weights in ``dtype``, made in one jitted program."""
    shapes = param_shapes(c, dtype)
    return jax.jit(lambda k: draw(k, shapes, _law))(key)


def _rope(x, theta):
    """Rotate-half RoPE over ``x [S, H, D]`` at positions 0..S-1."""
    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None, None] * inv       # [S,1,D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, quant):
    """Causal softmax attention; q ``[S, nq, D]``, k/v ``[S, nkv, D]``;
    query head ``j`` reads kv head ``j // (nq // nkv)``."""
    S, nq, D = q.shape
    group = nq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    qb = min(QBLOCK, S)
    kpos = jnp.arange(S)

    def block(args):
        q_blk, qpos = args                                     # [qb,nq,D]
        s = matmul("qhd,khd->hqk", q_blk, k, quant) * D ** -0.5
        s = jnp.where(qpos[None, :, None] >= kpos[None, None, :], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return matmul("hqk,khd->qhd", p, v, quant)

    out = jax.lax.map(block, (q.reshape(S // qb, qb, nq, D),
                              jnp.arange(S).reshape(S // qb, qb)))
    return out.reshape(S, nq * D)


def make_forward(c, quant: bool = False):
    """``forward(weights, tokens [S]) -> logits [S, V]`` in f32.

    ``quant`` rounds every matmul operand and the embedding rows to
    fp8: the control, which the comparison has to reject.
    """
    k = dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    nq, nkv, hd = k["nq"], k["nkv"], k["hd"]

    def w(x):
        return x.astype(F32)

    @jax.jit
    def embed(table, tokens):
        x = jnp.take(table, tokens, axis=0).astype(F32)
        return fp8(x) if quant else x

    @jax.jit
    def layer(blocks, i, x):
        p = jax.tree.map(lambda a: a[i], blocks["sub0"])
        S = x.shape[0]
        h = rms_norm(x, p["ln1"]["scale"], eps)
        m = p["mixer"]
        q = matmul("sd,dk->sk", h, w(m["wq"]), quant).reshape(S, nq, hd)
        kk = matmul("sd,dk->sk", h, w(m["wk"]), quant).reshape(S, nkv, hd)
        v = matmul("sd,dk->sk", h, w(m["wv"]), quant).reshape(S, nkv, hd)
        q = _rope(rms_norm(q, m["q_norm"], eps), theta)
        kk = _rope(rms_norm(kk, m["k_norm"], eps), theta)
        o = _attention(q, kk, v, quant)
        x = x + matmul("sk,kd->sd", o, w(m["wo"]), quant)
        h = rms_norm(x, p["ln2"]["scale"], eps)
        f = p["ffn"]
        g = matmul("sd,df->sf", h, w(f["wg"]), quant)
        u = matmul("sd,df->sf", h, w(f["wi"]), quant)
        return x + matmul("sf,fd->sd", jax.nn.silu(g) * u, w(f["wo"]),
                          quant)

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

    ``block_flops``: the matmuls of one block (2 per multiply-add) plus
    causal attention, which needs ``S(S+1)/2`` query-key pairs for the
    scores and as many for the weighted values.  ``block_bytes``: the
    block's weights, each read once.  ``act_bytes``: one ``[S, d]``
    activation.  Norms, RoPE and softmax are elementwise and counted
    in neither.
    """
    k = dims(c)
    d, nq, nkv, hd, ff, V, L = (k[n] for n in
                                ("d", "nq", "nkv", "hd", "ff", "V", "L"))
    S = seq
    proj = 2 * S * d * (nq * hd + 2 * nkv * hd) + 2 * S * nq * hd * d
    mlp = 2 * S * d * ff * 3
    attn = 2 * 2 * nq * hd * S * (S + 1) // 2
    shapes = param_shapes(c, dtype)
    per_block = tree_bytes(shapes["blocks"]) // L
    itemsize = jnp.dtype(dtype).itemsize
    return {
        "block_flops": proj + mlp + attn,
        "block_bytes": per_block,
        "act_bytes": S * d * itemsize,
        "head_flops": 2 * S * d * V,
        "num_blocks": L,
    }
