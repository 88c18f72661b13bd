"""Pieces shared by the plain references: float32 arithmetic at full
matmul precision, the lower-precision control, seeded weights.

Nothing here imports the program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: Largest finite float8_e4m3fn value.
FP8_MAX = 448.0


def fp8(x):
    """Round ``x`` to float8 e4m3 under one per-tensor scale, back in
    f32: the operand precision of the control (the nearest precision
    below the configuration's bfloat16)."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def matmul(spec: str, a, b, quant: bool):
    """``einsum(spec, a, b)`` in f32 at HIGHEST precision; with
    ``quant`` both operands are first rounded to fp8."""
    a, b = a.astype(F32), b.astype(F32)
    if quant:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def weight_key(seed: int):
    """A PRNG key for the weights from any whole-number seed (seeds may
    exceed 32 bits): RBG bits are cheap to draw on the TPU."""
    s = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.key(s & 0x7FFFFFFF, impl="rbg")


def tree_bytes(shapes) -> int:
    """Bytes of a tree of ``jax.ShapeDtypeStruct``."""
    return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
               for s in jax.tree.leaves(shapes))


def draw(key, shapes, rules):
    """Weights for the tree ``shapes`` (``ShapeDtypeStruct`` leaves).

    ``rules(path, shape)`` names the leaf's law: ``("normal", std)``
    (drawn in the leaf's dtype), ``("near_one", std)`` (1 + std *
    normal) or ``("log_uniform", lo, hi)`` (drawn in f32 and cast),
    inside the caller's jit.
    """
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, s) in zip(keys, leaves):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        law = rules(name, s.shape)
        if law[0] == "normal":
            v = jax.random.normal(k, s.shape, s.dtype) * law[1]
        elif law[0] == "near_one":
            v = 1.0 + jax.random.normal(k, s.shape, F32) * law[1]
        elif law[0] == "log_uniform":
            v = jnp.exp(jax.random.uniform(k, s.shape, F32,
                                           np.log(law[1]), np.log(law[2])))
        else:
            raise ValueError(f"unknown law {law!r} for {name}")
        out.append(v.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
