"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend every wrapper runs the kernel in interpret mode; on a
TPU the same call sites emit Mosaic kernels.  The flag is
resolved lazily *per call* through :func:`repro.kernels.runtime
.interpret_mode` and enters each jit as a static argument, so toggling
it (tests, the pallas fabric engine) selects a different trace instead
of reusing a stale one baked in at import.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import bucket_pack as _bp
from . import flash_attention as _fa
from . import quant8 as _q8
from . import runtime as _rt


def __getattr__(name):
    # Backward-compatible module attribute: ``ops.INTERPRET`` used to be
    # frozen at import time; now it reflects the live resolver.
    if name == "INTERPRET":
        return _rt.interpret_mode()
    raise AttributeError(name)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k",
    "interpret"))
def _flash_attention(q, k, v, *, causal, window, softcap, scale,
                     block_q, block_k, interpret):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128):
    return _flash_attention(q, k, v, causal=causal, window=window,
                            softcap=softcap, scale=scale, block_q=block_q,
                            block_k=block_k, interpret=_rt.interpret_mode())


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _bucket_pack(leaves, out_dtype, interpret):
    return _bp.bucket_pack(list(leaves), out_dtype=out_dtype,
                           interpret=interpret)


def bucket_pack(leaves: Sequence[jax.Array], out_dtype=None):
    return _bucket_pack(tuple(leaves), out_dtype,
                        interpret=_rt.interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bucket_unpack(flat, templates, interpret):
    return _bp.bucket_unpack(flat, templates, interpret=interpret)


def bucket_unpack(flat, templates):
    return _bucket_unpack(flat, templates, interpret=_rt.interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _quantize_blockwise(x, interpret):
    return _q8.quantize_blockwise(x, interpret=interpret)


def quantize_blockwise(x):
    return _quantize_blockwise(x, interpret=_rt.interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dequantize_blockwise(q, scales, interpret):
    return _q8.dequantize_blockwise(q, scales, interpret=interpret)


def dequantize_blockwise(q, scales):
    return _dequantize_blockwise(q, scales, interpret=_rt.interpret_mode())
