"""Jit'd dispatch wrappers for the Pallas kernels.

``impl`` selection (the caller names it; nothing is picked from the
backend, so a kernel never silently becomes its reference off the TPU):
  * "pallas"     — compiled Pallas (TPU; the default)
  * "interpret"  — Pallas interpret mode (CPU validation; executes the
                   kernel body in Python via the Pallas interpreter)
  * "ref"        — pure-jnp oracle (XLA)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import ref as ref_lib
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas

IMPLS = ("pallas", "interpret", "ref")


def _check(impl: str) -> None:
    if impl not in IMPLS:
        raise ValueError(f"impl={impl!r}; pick one of {IMPLS}")


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, impl: str = "pallas",
                    block_q: int = 128, block_k: int = 128):
    _check(impl)
    if impl == "ref":
        return ref_lib.flash_attention_ref(q, k, v, causal=causal,
                                           window=window)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         block_q=block_q, block_k=block_k,
                         interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("window", "impl", "block_k"))
def decode_attention(q, k, v, index, *, window: Optional[int] = None,
                     impl: str = "pallas", block_k: int = 512):
    _check(impl)
    if impl == "ref":
        return ref_lib.decode_attention_ref(q, k, v, index, window=window)
    return _decode_pallas(q, k, v, index, window=window, block_k=block_k,
                          interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("chunk", "block_h", "impl"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, block_h: int = 8,
             impl: str = "pallas"):
    _check(impl)
    if impl == "ref":
        return ref_lib.ssd_scan_ref(x, dt, A, B, C)
    return _ssd_pallas(x, dt, A, B, C, chunk=chunk, block_h=block_h,
                       interpret=(impl == "interpret"))
