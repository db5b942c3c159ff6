"""Attention mixers: GQA (sliding window, softcap, M-RoPE) and MLA.

All attention goes through ``masked_attention``, which scans over *query
chunks* so the (B, H, Sq, Sk) score matrix never materializes — at 32k
context a naive softmax would need ~8 GB/chip of scores.  Each chunk's
softmax is exact, so this is numerically identical to the reference
formulation.  Where the positions are the token indices (training, no
cache) a chunk scores only the key blocks the causal mask leaves it, the
prefix up to its own last row; with position arrays (a cache, per-batch
rows) it scores the full key range.  The Pallas flash-attention kernel
(repro.kernels.flash_attention) is the TPU-tiled version of the same
contraction.  The backward of the chunked path (a custom VJP) keeps only
q, k, v, the output and each row's log-sum-exp, and recomputes one
chunk's scores at a time, so neither pass holds more than one chunk's
scores.  A call of one chunk is one block under plain autodiff, whose
backward keeps that block's scores.

Head-count padding for tensor parallelism: query heads may be padded up to
a multiple of the TP degree; padded slots are zero-initialized in both the
input and output projections so the layer output equals the logical
head-count output exactly.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import apply_rope, dense_init, rms_norm, softcap

NEG_INF = -2.3819763e38  # most-negative bf16-representable


def head_to_kv_map(n_heads: int, n_kv: int, n_heads_padded: int) -> Tuple[int, ...]:
    """Static q-head -> kv-head assignment; padded heads map to kv 0."""
    group = n_heads // n_kv
    return tuple((h // group) if h < n_heads else 0
                 for h in range(n_heads_padded))


def _mask(q_pos, k_pos, window):
    """Boolean (…, Sq, Sk): causal + optional sliding window (<=0: global)."""
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    m = k <= q
    window = jnp.asarray(window)
    return m & jnp.where(window > 0, (q - k) < window, True)


def _scores(q, k, q_pos, k_pos, window, cap, scale):
    """Scaled, soft-capped f32 scores (B,Kv,G,Sq,Sk) of q: (B,Sq,H,D)
    against k: (B,Sk,Kv,D), Kv | H, and the mask that keeps them."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, sq, kv, h // kv, d)
    # f32 accumulation via preferred_element_type: casting the result
    # instead makes XLA convert the OPERANDS to f32 — measured to
    # materialize a full f32 copy of the KV cache on decode cells.
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * scale
    scores = softcap(scores, cap)
    if q_pos.ndim == 1:
        m = _mask(q_pos, k_pos, window)[None, None, None]
    else:  # per-batch positions (decode)
        m = _mask(q_pos, k_pos[None, :], window)[:, None, None]
    return scores, m


def _attn_block(q, k, v, q_pos, k_pos, window, cap, scale, out_dtype):
    """q: (B,Sq,H,D); k/v: (B,Sk,Kv,D) with Kv | H — grouped einsums, the
    expanded (B,Sk,H,D) KV is never materialized (at 32k decode that
    expansion was ~2 GiB x2 per layer)."""
    b, sq, h, _ = q.shape
    scores, m = _scores(q, k, q_pos, k_pos, window, cap, scale)
    scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, -1)


def masked_attention(q, k, v, *, q_pos=None, k_pos=None, window=0,
                     attn_softcap: Optional[float] = None,
                     scale: float, q_chunk: int = 512) -> jax.Array:
    """q: (B,Sq,H,Dk), k: (B,Sk,Kv,Dk), v: (B,Sk,Kv,Dv), Kv | H (uniform
    grouping: q head i attends kv head i // (H/Kv)) -> (B,Sq,H,Dv).

    ``q_pos`` and ``k_pos`` None: the positions are the token indices, a
    self-attention over one sequence (Sq == Sk).  Position arrays (a
    cache, per-batch rows, M-RoPE) are taken as they are.

    Scans over query chunks, each chunk's softmax exact.  With position
    arrays each chunk scores the full key range; with index positions
    chunk i scores only the keys ``[0, (i+1) q_chunk)`` it can see, since
    the causal mask takes every later key block whole.  The chunked
    path's backward recomputes each chunk's scores (``_chunked``).
    """
    sq = q.shape[1]
    short = sq <= q_chunk or sq % q_chunk != 0
    if q_pos is None and short:
        q_pos = k_pos = jnp.arange(sq)
    if short or (q_pos is not None and q_pos.ndim > 2):
        return _attn_block(q, k, v, q_pos, k_pos, window, attn_softcap,
                           scale, q.dtype)
    return _chunked(q, k, v, q_pos, k_pos, window, attn_softcap, scale,
                    q_chunk)


_RECOMPUTE = {"chunked_vjp": 0}
_BLOCKS = {"scored": 0, "grid": 0}


def recompute_stats() -> dict:
    """Chunked attention calls traced with the recompute backward."""
    return dict(_RECOMPUTE)


def block_stats() -> dict:
    """Key blocks (``q_chunk`` keys wide) that the traced chunked passes
    score, and the blocks of their full (query chunk, key block) grids;
    each traced primal, forward and backward adds its own."""
    return dict(_BLOCKS)


def _count_blocks(q_pos, nc, sk, c):
    grid = nc * -(-sk // c)
    _BLOCKS["grid"] += grid
    _BLOCKS["scored"] += grid if q_pos is not None else nc * (nc + 1) // 2


def _prefixes(nc, c):
    """Index positions: query chunk i's rows, the end of the key prefix it
    can see, and the positions of both."""
    for i in range(nc):
        hi = (i + 1) * c
        yield slice(i * c, hi), hi, jnp.arange(i * c, hi), jnp.arange(hi)


def _split(x, nc):
    """(B, Sq, ...) -> (nc, B, Sq/nc, ...): the query chunks as scan xs."""
    b, sq = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, nc, sq // nc, *x.shape[2:]), 1, 0)


def _merge(x):
    """(nc, B, c, ...) -> (B, nc * c, ...): the inverse of ``_split``."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def _chunk_pos(q_pos, nc):
    """1-D (Sq,) -> (nc, c); per-batch (B, Sq), e.g. M-RoPE -> (nc, B, c)."""
    if q_pos.ndim == 1:
        return q_pos.reshape(nc, -1)
    return _split(q_pos, nc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _chunked(q, k, v, q_pos, k_pos, window, cap, scale, q_chunk):
    """The query-chunk scan.  Its backward (FlashAttention-2's at chunk
    granularity) keeps ``q, k, v``, the output and each row's
    log-sum-exp, and recomputes a chunk's scores from them: autodiff of
    the scan would stack every chunk's f32 scores, (Sq, Sk) per head.
    Index positions (``q_pos`` None) unroll the chunks, each over its
    static key prefix."""
    nc = q.shape[1] // q_chunk
    _count_blocks(q_pos, nc, k.shape[1], q_chunk)
    if q_pos is None:
        return jnp.concatenate(
            [_attn_block(q[:, rows], k[:, :hi], v[:, :hi], qp, kp, window,
                         cap, scale, q.dtype)
             for rows, hi, qp, kp in _prefixes(nc, q_chunk)], axis=1)

    def body(_, xs):
        qc, pc = xs
        return (), _attn_block(qc, k, v, pc, k_pos, window, cap, scale,
                               q.dtype)

    _, out = jax.lax.scan(body, (), (_split(q, nc), _chunk_pos(q_pos, nc)))
    return _merge(out)


def _chunk_fwd(qc, k, v, pc, k_pos, window, cap, scale):
    """One query chunk's output and each row's log-sum-exp (B,Kv,G,c)."""
    s, m = _scores(qc, k, pc, k_pos, window, cap, scale)
    # NEG_INF as a function of s: under a remat'd layer a constant fill
    # is hoisted out of the scan into a tile-sized buffer of its own,
    # outside the caller's scope
    s = jnp.where(m, s, jnp.minimum(s, NEG_INF))
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - top)
    tot = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqs,bskd->bqkgd", (e / tot).astype(qc.dtype), v)
    return o.reshape(*qc.shape[:3], -1), (top + jnp.log(tot))[..., 0]


def _chunked_fwd(q, k, v, q_pos, k_pos, window, cap, scale, q_chunk):
    """The same chunks, with each row's log-sum-exp (nc, B,Kv,G,c)."""
    nc = q.shape[1] // q_chunk
    _count_blocks(q_pos, nc, k.shape[1], q_chunk)
    if q_pos is None:
        out, lse = zip(*(_chunk_fwd(q[:, rows], k[:, :hi], v[:, :hi], qp,
                                    kp, window, cap, scale)
                         for rows, hi, qp, kp in _prefixes(nc, q_chunk)))
        out, lse = jnp.concatenate(out, axis=1), jnp.stack(lse)
    else:
        def body(_, xs):
            qc, pc = xs
            return (), _chunk_fwd(qc, k, v, pc, k_pos, window, cap, scale)

        _, (out, lse) = jax.lax.scan(body, (), (_split(q, nc),
                                                _chunk_pos(q_pos, nc)))
        out = _merge(out)
    return out, (q, k, v, q_pos, k_pos, window, out, lse)


def _chunk_bwd(qc, k, v, pc, k_pos, window, cap, scale, lc, doc, rc):
    """One query chunk's dq and its f32 dk, dv over the keys it scored."""
    b, c, h, _ = qc.shape
    kv = k.shape[2]
    f32 = jnp.float32
    qg = qc.reshape(b, c, kv, h // kv, -1)
    dog = doc.reshape(b, c, kv, h // kv, -1)
    t, m = _scores(qc, k, pc, k_pos, window, cap, scale)
    p = jnp.exp(jnp.where(m, t, NEG_INF) - lc[..., None])
    dv = jnp.einsum("bkgqs,bqkgd->bskd", p.astype(qc.dtype), dog,
                    preferred_element_type=f32)
    dp = jnp.einsum("bqkgd,bskd->bkgqs", dog, v, preferred_element_type=f32)
    ds = p * (dp - jnp.moveaxis(rc, 1, -1)[..., None])
    if cap is not None:  # d(cap tanh(x / cap))/dx = 1 - tanh^2
        ds = ds * (1.0 - jnp.square(t / cap))
    ds = ds * scale
    dq = jnp.einsum("bkgqs,bskd->bqkgd", ds, k, preferred_element_type=f32)
    dk = jnp.einsum("bkgqs,bqkgd->bskd", ds, qg, preferred_element_type=f32)
    return dq.reshape(qc.shape).astype(qc.dtype), dk, dv


def _chunked_bwd(cap, scale, q_chunk, res, d_out):
    """Per chunk: p = exp(s - lse); dv += p^T dO; dp = dO v^T;
    ds = p (dp - rowsum(dO o)) through the softcap and the scale;
    dq = ds k; dk += ds^T q, summed over each kv head's query group.
    dk and dv accumulate in f32, with index positions into the key
    prefix each chunk scored."""
    q, k, v, q_pos, k_pos, window, out, lse = res
    _RECOMPUTE["chunked_vjp"] += 1
    b, sq, h, _ = q.shape
    kv = k.shape[2]
    nc = sq // q_chunk
    _count_blocks(q_pos, nc, k.shape[1], q_chunk)
    f32 = jnp.float32
    # rowsum(dO * o), the softmax backward's row term: (B, Sq, Kv, G)
    rows = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1)
    rows = rows.reshape(b, sq, kv, h // kv)
    dk_acc, dv_acc = jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)

    if q_pos is None:
        dq = []
        for i, (r, hi, qp, kp) in enumerate(_prefixes(nc, q_chunk)):
            # a chunk's operands wait for the sums of the one before: left
            # free, XLA interleaves several chunks' score tiles (2.8x the
            # scan's temporaries for one MLA layer, compiled for a v5e)
            xs, dk_acc, dv_acc = jax.lax.optimization_barrier(
                ((q[:, r], k[:, :hi], v[:, :hi], lse[i], d_out[:, r],
                  rows[:, r]), dk_acc, dv_acc))
            qc, kc, vc, lc, doc, rc = xs
            dqc, dk, dv = _chunk_bwd(qc, kc, vc, qp, kp, window, cap, scale,
                                     lc, doc, rc)
            dk_acc = jax.lax.dynamic_update_slice_in_dim(
                dk_acc, dk_acc[:, :hi] + dk, 0, axis=1)
            dv_acc = jax.lax.dynamic_update_slice_in_dim(
                dv_acc, dv_acc[:, :hi] + dv, 0, axis=1)
            dq.append(dqc)
        dq = jnp.concatenate(dq, axis=1)
    else:
        def body(acc, xs):
            qc, pc, lc, doc, rc = xs
            dqc, dk, dv = _chunk_bwd(qc, k, v, pc, k_pos, window, cap,
                                     scale, lc, doc, rc)
            return (acc[0] + dk, acc[1] + dv), dqc

        (dk_acc, dv_acc), dq = jax.lax.scan(
            body, (dk_acc, dv_acc),
            (_split(q, nc), _chunk_pos(q_pos, nc), lse, _split(d_out, nc),
             _split(rows, nc)))
        dq = _merge(dq)
    return (dq, dk_acc.astype(k.dtype), dv_acc.astype(v.dtype),
            None, None, None)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_attention(key, *, d_model: int, n_heads: int, n_heads_padded: int,
                   n_kv: int, head_dim: int, qkv_bias: bool, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    wq = dense_init(ks[0], d_model, (n_heads_padded, head_dim), dtype)
    wo = dense_init(ks[3], n_heads_padded * head_dim, (d_model,), dtype
                    ).reshape(n_heads_padded, head_dim, d_model)
    if n_heads_padded > n_heads:  # zero padded slots -> exact logical output
        wq = wq.at[:, n_heads:, :].set(0.0)
        wo = wo.at[n_heads:, :, :].set(0.0)
    p = {
        "wq": wq,
        "wk": dense_init(ks[1], d_model, (n_kv, head_dim), dtype),
        "wv": dense_init(ks[2], d_model, (n_kv, head_dim), dtype),
        "wo": wo,
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads_padded, head_dim), dtype)
        p["bk"] = jnp.zeros((n_kv, head_dim), dtype)
        p["bv"] = jnp.zeros((n_kv, head_dim), dtype)
    return p


def attention_fwd(p: Dict, x: jax.Array, *, positions: jax.Array,
                  head_map: Tuple[int, ...], window=0,
                  attn_softcap: Optional[float] = None,
                  rope_theta: float = 1e4,
                  mrope_sections: Optional[Tuple[int, ...]] = None,
                  q_scale: Optional[float] = None,
                  cache: Optional[Dict] = None,
                  cache_pos: Optional[jax.Array] = None,
                  q_chunk: int = 512,
                  decode_attn=None,
                  ) -> Tuple[jax.Array, Optional[Dict]]:
    """GQA attention.

    ``decode_attn(q (B,H,D), k (B,S,Kv,D), v, pos, window) -> (B,H,D)``:
    optional partitioned-KV decode path (shard_map flash decode) used for
    single-token steps when provided.

    x: (B, S, D).  positions: (B, S), (S,)-broadcastable, or (3, B, S) for
    M-RoPE.  cache: {'k','v'}: (B, S_max, n_kv, head_dim) with scalar write
    offset ``cache_pos``.
    """
    head_dim = p["wq"].shape[-1]
    scale = q_scale if q_scale is not None else head_dim ** -0.5

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]

    q = apply_rope(q, positions, rope_theta, mrope_sections)
    k = apply_rope(k, positions, rope_theta, mrope_sections)
    tpos = positions if mrope_sections is None else positions[0]

    if cache is not None:
        assert cache_pos is not None
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, cache_pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, cache_pos, 0, 0))
        cache = {"k": ck, "v": cv}
        k, v = ck, cv
        k_pos = jnp.arange(k.shape[1])
        q_pos = tpos if tpos.ndim >= 1 else tpos[None]
    else:  # the token indices
        q_pos = k_pos = None

    n_kv = k.shape[2]
    h_padded = q.shape[2]
    uniform = (h_padded % n_kv == 0 and
               tuple(head_map) == tuple(i // (h_padded // n_kv)
                                        for i in range(h_padded)))
    if (decode_attn is not None and cache is not None and q.shape[1] == 1
            and uniform):
        out = decode_attn(q[:, 0], k, v, pos=cache_pos + 0,
                          window=window, attn_softcap=attn_softcap,
                          scale=scale)
        out = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None, :]
        return out, cache
    if uniform:
        # grouped path: no expanded-KV materialization
        k_att, v_att = k, v
    else:
        # padded/non-uniform head map (e.g. qwen2's 28->32): fall back to
        # explicit expansion via gather
        hm = jnp.asarray(head_map, dtype=jnp.int32)
        k_att = jnp.take(k, hm, axis=2)
        v_att = jnp.take(v, hm, axis=2)

    out = masked_attention(q, k_att, v_att, q_pos=q_pos, k_pos=k_pos,
                           window=window, attn_softcap=attn_softcap,
                           scale=scale, q_chunk=q_chunk)
    out = jnp.einsum("bqhk,hkd->bqd", out, p["wo"])
    return out, cache


# ---------------------------------------------------------------------------
# Multi-head latent attention (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------

def init_mla(key, *, d_model: int, n_heads_padded: int, n_heads: int,
             q_lora: Optional[int], kv_lora: int, qk_nope: int,
             qk_rope: int, v_dim: int, dtype) -> Dict:
    """``q_lora`` None: the query is one projection ``w_q`` (DeepSeek's
    ``q_lora_rank`` null), else a low-rank ``w_dq`` -> norm -> ``w_uq``."""
    ks = jax.random.split(key, 7)
    wq = dense_init(ks[1], q_lora or d_model,
                    (n_heads_padded, qk_nope + qk_rope), dtype)
    wo = dense_init(ks[6], n_heads_padded * v_dim, (d_model,), dtype
                    ).reshape(n_heads_padded, v_dim, d_model)
    if n_heads_padded > n_heads:
        wq = wq.at[:, n_heads:, :].set(0.0)
        wo = wo.at[n_heads:, :, :].set(0.0)
    q = {"w_q": wq} if q_lora is None else {
        "w_dq": dense_init(ks[0], d_model, (q_lora,), dtype),
        "norm_q": jnp.ones((q_lora,), dtype),
        "w_uq": wq}
    return {
        **q,
        "w_dkv": dense_init(ks[2], d_model, (kv_lora,), dtype),
        "norm_kv": jnp.ones((kv_lora,), dtype),
        "w_uk": dense_init(ks[3], kv_lora, (n_heads_padded, qk_nope), dtype),
        "w_uv": dense_init(ks[4], kv_lora, (n_heads_padded, v_dim), dtype),
        "w_kr": dense_init(ks[5], d_model, (qk_rope,), dtype),
        "wo": wo,
    }


def mla_fwd(p: Dict, x: jax.Array, *, positions: jax.Array, qk_nope: int,
            qk_rope: int, rope_theta: float = 1e4, window=0,
            cache: Optional[Dict] = None,
            cache_pos: Optional[jax.Array] = None, q_chunk: int = 512,
            eps: float = 1e-6,
            ) -> Tuple[jax.Array, Optional[Dict]]:
    """MLA: the KV cache stores only the compressed latent + shared rope key.

    cache: {'ckv': (B, S_max, kv_lora), 'kr': (B, S_max, qk_rope)}.
    MLA's latent is itself an *aggregated* per-token buffer — the
    architecture-level cousin of the paper's message aggregation.
    Rotary positions turn the ``qk_rope`` dims as halves (rotate-half);
    DeepSeek's checkpoints pair them interleaved, which is a fixed
    permutation of the rope columns of the query projection and ``w_kr``.
    """
    scale = (qk_nope + qk_rope) ** -0.5
    if "w_q" in p:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
    else:
        cq = rms_norm(x @ p["w_dq"], p["norm_q"], eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = apply_rope(q_rope, positions, rope_theta)

    ckv = rms_norm(x @ p["w_dkv"], p["norm_kv"], eps)     # (B, S, r)
    kr = apply_rope((x @ p["w_kr"])[:, :, None, :], positions,
                    rope_theta)[:, :, 0, :]               # (B, S, qk_rope)

    if cache is not None:
        assert cache_pos is not None
        ckv_full = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, cache_pos, 0))
        kr_full = jax.lax.dynamic_update_slice(
            cache["kr"], kr.astype(cache["kr"].dtype), (0, cache_pos, 0))
        cache = {"ckv": ckv_full, "kr": kr_full}
        ckv_att, kr_att = ckv_full, kr_full
        k_pos = jnp.arange(ckv_full.shape[1])
        q_pos = positions if positions.ndim >= 1 else positions[None]
    else:  # the token indices
        ckv_att, kr_att = ckv, kr
        q_pos = k_pos = None

    k_nope = jnp.einsum("bsr,rhk->bshk", ckv_att, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv_att, p["w_uv"])

    # Fold the shared rope key into the head dim so one attention call works:
    # scores = q_nope . k_nope + q_rope . kr
    h = q.shape[2]
    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
    kr_b = jnp.broadcast_to(kr_att[:, :, None, :],
                            (*kr_att.shape[:2], h, qk_rope))
    k_cat = jnp.concatenate([k_nope, kr_b], axis=-1)

    out = masked_attention(q_cat, k_cat, v, q_pos=q_pos, k_pos=k_pos,
                           window=window, attn_softcap=None, scale=scale,
                           q_chunk=q_chunk)
    out = jnp.einsum("bqhk,hkd->bqd", out, p["wo"])
    return out, cache
