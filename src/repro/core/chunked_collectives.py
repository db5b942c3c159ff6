"""Partition-granular ring collectives with multi-channel streams.

These are the manual (shard_map) counterparts of XLA's fused collectives,
exposing the paper's two remaining knobs that psum cannot express:

  * **partitioning**: a collective is decomposed into per-partition
    ``ppermute`` steps, so each partition's payload can be consumed the
    moment it arrives (collective matmul), and
  * **channels** (VCI analogue): the payload is split into ``n_channels``
    interleaved streams, each circulating on its own ppermute chain —
    distinct XLA channel ids — mirroring MPICH's round-robin
    partition->VCI mapping (§3.2.2).

Also here: an int8-quantized ring all-reduce (gradient compression over
the wire, requantized per hop) used by the optimizer's ``compress`` hook.

All functions must run inside ``shard_map`` with ``axis`` manual.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .commplan import channel_slices


def _ring_perm(n: int, reverse: bool = False):
    if reverse:
        return [(i, (i - 1) % n) for i in range(n)]
    return [(i, (i + 1) % n) for i in range(n)]


def _split_channels(x: jax.Array, k: int):
    """Split leading dim into k interleaved streams (CommPlan round-robin)."""
    if k <= 1:
        return [x]
    assert x.shape[0] % k == 0, (x.shape, k)
    return [x[sl] for sl in channel_slices(x.shape[0], k)]


def _merge_channels(parts, k: int, axis: int = 0):
    """Inverse of _split_channels: re-interleave k streams along ``axis``."""
    if k <= 1:
        return parts[0]
    n = sum(p.shape[axis] for p in parts)
    out = jnp.zeros((*parts[0].shape[:axis], n, *parts[0].shape[axis + 1:]),
                    parts[0].dtype)
    idx = [slice(None)] * out.ndim
    for sl, p in zip(channel_slices(n, k), parts):
        idx[axis] = sl
        out = out.at[tuple(idx)].set(p)
    return out


def ring_all_gather(x: jax.Array, axis: str, *, n_channels: int = 1,
                    tiled: bool = False) -> jax.Array:
    """All-gather via N-1 ppermute steps per channel stream.

    x: the local shard.  Returns (N, *x.shape) stacked in global rank
    order, or concatenated along dim 0 if ``tiled``.
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = _ring_perm(n)

    def gather_one(stream):
        blocks = [stream]
        cur = stream
        for _ in range(n - 1):
            cur = jax.lax.ppermute(cur, axis, perm)
            blocks.append(cur)
        stacked = jnp.stack(blocks)          # [j] = shard of rank (i - j)
        order = (idx - jnp.arange(n)) % n    # out[g] = stacked[(i - g) % n]
        return jnp.take(stacked, order, axis=0)

    streams = [gather_one(s) for s in _split_channels(x, n_channels)]
    if n_channels == 1:
        out = streams[0]
    else:  # reassemble each gathered shard from its interleaved streams
        out = jnp.stack([_merge_channels([s[g] for s in streams], n_channels)
                         for g in range(n)])
    return out.reshape(-1, *x.shape[1:]) if tiled else out


def ring_reduce_scatter(x: jax.Array, axis: str, *, n_channels: int = 1
                        ) -> jax.Array:
    """Reduce-scatter via a ring: x is (N, chunk, ...) of local
    contributions in global order; returns this rank's reduced chunk."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = _ring_perm(n)

    def rs_one(stream):  # stream: (N, chunk, ...)
        # The partial for block b is created at rank b+1 (each rank r
        # starts with its contribution to block r-1) and travels n-1 hops;
        # after hop s, rank r holds the partial for block r-s-1 and adds
        # its local contribution.  After n-1 hops rank r holds block r,
        # fully reduced over all ranks.
        acc = jnp.take(stream, (idx - 1) % n, axis=0)
        for s in range(1, n):
            acc = jax.lax.ppermute(acc, axis, perm)
            acc = acc + jnp.take(stream, (idx - s - 1) % n, axis=0)
        return acc

    if n_channels > 1:  # channel split applies to the chunk dim (dim 1)
        parts = [x[:, sl] for sl in channel_slices(x.shape[1], n_channels)]
        return _merge_channels([rs_one(p) for p in parts], n_channels,
                               axis=0)
    return rs_one(x)


def ring_all_reduce(x: jax.Array, axis: str, *, n_channels: int = 1
                    ) -> jax.Array:
    """All-reduce = reduce-scatter + all-gather over flat chunks."""
    n = jax.lax.axis_size(axis)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % (n * max(1, n_channels))
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)
    mine = ring_reduce_scatter(chunks, axis, n_channels=n_channels)
    full = ring_all_gather(mine, axis, n_channels=n_channels, tiled=True)
    full = full.reshape(-1)
    if pad:
        full = full[:-pad]
    return full.reshape(x.shape)


def ring_all_reduce_q8(x: jax.Array, axis: str) -> jax.Array:
    """Int8-compressed ring all-reduce: each hop ships int8 payloads +
    one f32 scale (4x wire-byte reduction vs f32), requantizing per hop.

    Lossy; error bounded by per-hop quantization step.  The analogue of
    aggressive gradient compression in the distributed-optimization bag of
    tricks; see optim.grad_compress for the error-feedback wrapper.
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = _ring_perm(n)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)

    def q(v):
        scale = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / 127.0
        return jnp.round(v / scale).astype(jnp.int8), scale

    def dq(qv, scale):
        return qv.astype(jnp.float32) * scale

    # reduce-scatter with quantized payloads
    acc = jnp.take(chunks, (idx - 1) % n, axis=0).astype(jnp.float32)
    for s in range(1, n):
        qv, sc = q(acc)
        qv = jax.lax.ppermute(qv, axis, perm)
        sc = jax.lax.ppermute(sc, axis, perm)
        acc = dq(qv, sc) + jnp.take(chunks, (idx - s - 1) % n,
                                    axis=0).astype(jnp.float32)
    # all-gather the reduced chunks, quantized
    qv, sc = q(acc)
    blocks = [(qv, sc)]
    for _ in range(n - 1):
        qv = jax.lax.ppermute(qv, axis, perm)
        sc = jax.lax.ppermute(sc, axis, perm)
        blocks.append((qv, sc))
    stacked = jnp.stack([dq(b, s) for b, s in blocks])
    order = (idx - jnp.arange(n)) % n
    full = jnp.take(stacked, order, axis=0).reshape(-1)
    if pad:
        full = full[:-pad]
    return full.reshape(x.shape).astype(x.dtype)


def collective_ag_matmul(x_shard: jax.Array, w: jax.Array, axis: str
                         ) -> jax.Array:
    """Overlapped all-gather + matmul (the serve-side early-bird pattern).

    Computes ``all_gather(x, axis) @ w`` but consumes each arriving shard
    immediately: at every ring step the freshly received x-block is
    multiplied while the next block is in flight — the MPI_Parrived-style
    per-partition consumption of §2.3.1, adapted to the MXU.

    x_shard: (rows_local, K); w: (K, N) (replicated or K-sharded upstream).
    Returns (axis_size * rows_local, N) in global row order.
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = _ring_perm(n)
    rows = x_shard.shape[0]
    out = jnp.zeros((n * rows, w.shape[1]), x_shard.dtype)
    cur = x_shard
    for j in range(n):
        src = (idx - j) % n  # whose shard we currently hold
        y = cur @ w
        out = jax.lax.dynamic_update_slice(
            out, y, (src * rows, jnp.zeros((), src.dtype)))
        if j != n - 1:
            cur = jax.lax.ppermute(cur, axis, perm)
    return out


def collective_matmul_rs(x: jax.Array, w_shard: jax.Array, axis: str
                         ) -> jax.Array:
    """Overlapped matmul + reduce-scatter.

    Each rank holds a K-shard of w (row-sharded contraction); the partial
    product is reduce-scattered over rows chunk-by-chunk so communication
    of chunk j overlaps the matmul of chunk j+1.

    x: (M, K_local); w_shard: (K_local, N).  Returns this rank's (M/n, N)
    chunk of the fully-reduced product (row-scattered in rank order).
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = _ring_perm(n)
    m = x.shape[0]
    assert m % n == 0
    rows = m // n

    def block(i):  # partial product of row-block i
        xb = jax.lax.dynamic_slice(x, (i * rows, 0), (rows, x.shape[1]))
        return xb @ w_shard

    acc = block((idx - 1) % n)
    for s in range(1, n):
        acc = jax.lax.ppermute(acc, axis, perm)
        acc = acc + block((idx - s - 1) % n)
    return acc
