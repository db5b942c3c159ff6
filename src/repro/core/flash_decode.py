"""Partitioned-KV decode attention (flash-decode with LSE combine).

The inference-side incarnation of partitioned communication: the KV cache
is the *global buffer*, sharded along the sequence axis across one or more
mesh axes.  Each chip computes attention of the (replicated, tiny) query
against its local KV partition independently — producing a partial output
plus softmax statistics — and the partitions are combined with a pair of
tiny collectives (max + sum) instead of all-gathering the cache.

Baseline GSPMD lowering of decode attention all-gathers the cache (or
per-head logits); this shard_map version reduces the collective bytes per
step from O(S * head_dim) to O(H * head_dim) — the hillclimb lever for the
decode-shape cells.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38

Axes = Union[str, Tuple[str, ...]]


def _axis_tuple(axis: Axes) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _flat_index(axes: Tuple[str, ...]) -> jax.Array:
    """Row-major rank of this device within the given mesh axes."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def flash_decode_shard(q: jax.Array, k_shard: jax.Array, v_shard: jax.Array,
                       *, axis: Axes, pos: jax.Array, window: int = 0,
                       attn_softcap: Optional[float] = None,
                       scale: float) -> jax.Array:
    """One-token GQA attention against a seq-sharded KV cache.

    Must run inside shard_map with ``axis`` manual (a name or tuple).
    q: (B, H, D) — replicated across ``axis``.
    k_shard/v_shard: (B, S_local, Kv, D), Kv | H — this device's sequence
    partition.
    pos: scalar current length (tokens at global index > pos are masked).
    Returns (B, H, D), identical on every rank of ``axis``.
    """
    axes = _axis_tuple(axis)
    idx = _flat_index(axes)
    b, h, d = q.shape
    s_local, kv = k_shard.shape[1], k_shard.shape[2]
    g = h // kv
    k_pos = idx * s_local + jnp.arange(s_local)          # global positions

    qg = q.reshape(b, kv, g, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_shard,
                        preferred_element_type=jnp.float32) * scale
    if attn_softcap is not None:
        scores = attn_softcap * jnp.tanh(scores / attn_softcap)
    valid = k_pos <= pos
    window = jnp.asarray(window)  # may be a traced per-layer scalar
    valid &= jnp.where(window > 0, (pos - k_pos) < window, True)
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)

    m_local = jnp.max(scores, axis=-1)                    # (B, Kv, G)
    m_global = m_local
    for a in axes:
        m_global = jax.lax.pmax(m_global, a)
    p = jnp.exp(scores - m_global[..., None])
    p = jnp.where(valid[None, None, None, :], p, 0.0)
    l_local = jnp.sum(p, axis=-1)                         # (B, Kv, G)
    o_local = jnp.einsum("bkgs,bskd->bkgd", p,
                         v_shard.astype(jnp.float32))

    l_global, o_global = l_local, o_local
    for a in axes:
        l_global = jax.lax.psum(l_global, a)
        o_global = jax.lax.psum(o_global, a)
    out = o_global / jnp.maximum(l_global, 1e-30)[..., None]
    return out.reshape(b, h, d).astype(q.dtype)


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     pos: jax.Array, window: int = 0,
                     attn_softcap: Optional[float] = None,
                     scale: float) -> jax.Array:
    """Single-device oracle (full KV): q (B,H,D), k/v (B,S,Kv,D)."""
    b, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    k_pos = jnp.arange(s)
    qg = q.reshape(b, kv, g, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if attn_softcap is not None:
        scores = attn_softcap * jnp.tanh(scores / attn_softcap)
    valid = k_pos <= pos
    window = jnp.asarray(window)
    valid &= jnp.where(window > 0, (pos - k_pos) < window, True)
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)
