"""Basic neural-net layers as pure functions over parameter pytrees.

No flax/haiku offline — parameters are plain nested dicts of jnp arrays,
initialized by ``init_*`` functions and consumed by pure ``*_fwd`` functions.
Sharding is attached externally (see ``repro.models.lm.param_specs``).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
             *, zero_centered: bool = False) -> jax.Array:
    """RMSNorm in f32 accumulation; ``zero_centered`` uses (1+scale) (gemma)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = (1.0 + scale) if zero_centered else scale
    return (x * w).astype(dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def swiglu(p, x: jax.Array) -> jax.Array:
    """SwiGLU MLP: (silu(x @ w_gate) * (x @ w_up)) @ w_down."""
    return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)


def dense_init(key, in_dim: int, out_shape: Sequence[int], dtype,
               scale: Optional[float] = None) -> jax.Array:
    """Truncated-normal fan-in init, shape (in_dim, *out_shape)."""
    if scale is None:
        scale = 1.0 / math.sqrt(in_dim)
    shape = (in_dim, *out_shape)
    return (scale * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def embed_init(key, vocab: int, d_model: int, dtype) -> jax.Array:
    """Std 1/sqrt(d): keeps tied-head logits O(1) at init (gemma/llama)."""
    return (jax.random.normal(key, (vocab, d_model), jnp.float32)
            / math.sqrt(d_model)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (classic + multimodal M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for half the head dim."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """Rotate ``x`` of shape (..., S, H, D) by position-dependent angles.

    ``positions``: (..., S) for classic RoPE, or (3, ..., S) for Qwen2-VL
    M-RoPE, in which case ``mrope_sections`` splits the D/2 frequency slots
    into (temporal, height, width) groups, each driven by its own position
    row.
    """
    half = x.shape[-1] // 2
    inv = rope_freqs(x.shape[-1], theta)  # (half,)
    if mrope_sections is None:
        ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, half)
    else:
        assert positions.ndim >= 2 and positions.shape[0] == 3, (
            "M-RoPE expects positions shaped (3, ..., S)")
        assert sum(mrope_sections) == half, (mrope_sections, half)
        ang_all = positions[..., None].astype(jnp.float32) * inv  # (3,...,S,half)
        chunks = []
        off = 0
        for i, sec in enumerate(mrope_sections):
            chunks.append(ang_all[i, ..., off:off + sec])
            off += sec
        ang = jnp.concatenate(chunks, axis=-1)  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Cross entropy, chunked over the sequence to bound logit memory
# ---------------------------------------------------------------------------

def chunked_cross_entropy(hidden: jax.Array, head: jax.Array,
                          labels: jax.Array, *, chunk: int = 512,
                          final_softcap: Optional[float] = None,
                          mask: Optional[jax.Array] = None,
                          valid_vocab: Optional[int] = None,
                          gather_targets: bool = False) -> jax.Array:
    """Mean CE of ``hidden @ head`` vs labels without materializing (B,S,V).

    hidden: (B, S, D); head: (D, V); labels: (B, S) int32.
    The (B, chunk, V) logits exist one chunk at a time inside a
    rematerialized scan — this is itself a partition-style optimization
    (the loss analogue of the paper's aggregation threshold), and remat
    keeps the backward pass from stashing per-chunk logits.
    ``valid_vocab``: mask logit columns >= this (TP vocab padding).
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    n_chunks = s // chunk
    rem = s - n_chunks * chunk
    if mask is None:
        mask = jnp.ones((b, s), dtype=jnp.float32)
    v = head.shape[-1]

    def chunk_loss(h, y, m):
        logits = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                            head.astype(jnp.float32))
        logits = softcap(logits, final_softcap)
        if valid_vocab is not None and valid_vocab < v:
            pad = jax.lax.broadcasted_iota(jnp.int32, (1, 1, v), 2)
            logits = jnp.where(pad < valid_vocab, logits, -jnp.inf)
        lse = jax.nn.logsumexp(logits, axis=-1)
        if gather_targets:
            tgt = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        else:
            # select+reduce instead of gather: stays vocab-sharded under
            # TP (take_along_axis over a sharded vocab makes GSPMD
            # all-gather the logits chunk — measured ~34 GiB/step/device
            # of all-gather traffic on the 4k-train cells).
            vids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, v), 2)
            tgt = jnp.sum(jnp.where(vids == y[..., None], logits, 0.0),
                          axis=-1)
        return jnp.sum((lse - tgt) * m), jnp.sum(m)

    @jax.checkpoint
    def body(carry, xs):
        tot, cnt = carry
        h, y, m = xs
        l, n = chunk_loss(h, y, m)
        return (tot + l, cnt + n), ()

    hs = hidden[:, :n_chunks * chunk].reshape(b, n_chunks, chunk, d)
    ys = labels[:, :n_chunks * chunk].reshape(b, n_chunks, chunk)
    ms = mask[:, :n_chunks * chunk].reshape(b, n_chunks, chunk)
    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)),
        (hs.transpose(1, 0, 2, 3), ys.transpose(1, 0, 2),
         ms.transpose(1, 0, 2)))
    if rem:
        l, n = chunk_loss(hidden[:, -rem:], labels[:, -rem:], mask[:, -rem:])
        tot, cnt = tot + l, cnt + n
    return tot / jnp.maximum(cnt, 1.0)
