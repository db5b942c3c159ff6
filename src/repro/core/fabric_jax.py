"""The compiled fabric engine: jitted stage scans + a vmapped grid path.

Third engine of the fabric family (``engine="jax"``).  It implements the
same three-stage resource model as :class:`repro.core.fabric.Fabric` —
per-rank VCI banks, per-rank NIC, per-directed-link wires — but advances
the grouped queue recurrences with ``jax.lax.scan`` over **fixed-shape
padded segment layouts** instead of a Python-level loop of NumPy steps:

  * each stage's jagged groups are padded to a ``(groups, depth)``
    matrix (depths rounded up to powers of two so jit traces are
    shared across nearby batch shapes; padded lanes are masked out of
    the carry, so padding never changes a value);
  * one jitted call advances all three stages — VCI scan carrying
    (busy-until, last-owner), NIC scan, wire scan — with the protocol
    classification (eager/bcopy/rendezvous, AM copy, put costs) as
    vectorized selects;
  * the **grid path** (:func:`transmit_grid`) stacks many independent
    cold-start exchanges (sweep points) into one extra leading axis and
    evaluates them with a single ``jax.vmap``-ed jit call — the whole
    (approach x theta x n_vcis x size) grid of a sweep spec in a few
    XLA dispatches instead of thousands of Python ones.

Precision contract (see :mod:`repro.compat`): under ``JAX_ENABLE_X64``
every array is float64 and all cost constants enter the jit as *dynamic*
scalars — XLA cannot constant-fold ``x / beta`` into a
multiply-by-reciprocal — so the engine is **bit-for-bit** identical to
``ReferenceFabric`` (pinned by ``tests/test_engine_jax.py``).  Under the
float32 default the same graph runs in single precision and is only
tolerance-close (~1e-4 relative on arrival times); counters
(``n_messages``, ``sent_per_rank``) stay exact in either mode.

Stage layouts are pure functions of the batch's (src, dst, vci) columns;
they are memoized per merge-equivalence key (the same key that memoizes
the stable merge sort in :mod:`repro.core.simulator`), so re-running a
scenario re-pays neither the sorts nor the grouping.

Streaming: the online ``advance`` path (inherited from
:class:`~repro.core.fabric.Fabric`) routes each admission wave of the
open-loop serving driver through ``transmit_arrays`` on the live warm
fabric — scalar state is authoritative between calls, and the pow2
depth quantization keeps repeated waves of nearby sizes on shared jit
traces instead of recompiling per wave.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Hashable, List, Optional, Tuple

import numpy as np

from . import fabric as _fb
from .fabric import Fabric, NetConfig, _group_layout

try:  # the engine is CPU-jax friendly; gate the import so the numpy
    import jax  # engines keep working on containers without jax
    import jax.numpy as jnp
    from jax import lax
    HAVE_JAX = True
except ImportError:  # pragma: no cover - exercised only without jax
    jax = jnp = lax = None
    HAVE_JAX = False


def _require_jax():
    if not HAVE_JAX:  # pragma: no cover
        raise ImportError(
            "engine='jax' needs jax installed; use engine='vector' (the "
            "batched NumPy engine) or engine='reference' instead")


def x64_enabled() -> bool:
    """float64 mode active (the bit-for-bit contract switch)."""
    _require_jax()
    from repro.compat import x64_enabled as _x64
    return _x64()


def _pow2(x: int) -> int:
    """Next power of two (>=1): quantizes pad shapes so jit traces are
    reused across nearby batch sizes instead of recompiling per shape."""
    return 1 << max(0, int(x) - 1).bit_length()


# ---------------------------------------------------------------------------
# Stage layouts: jagged groups -> fixed-shape padded matrices
# ---------------------------------------------------------------------------

# One stage's grouping of a batch: ``order`` permutes messages into
# group-major layout, ``counts``/``offsets`` delimit the groups, ``uniq``
# names each group's resource id (bank / rank / directed link).
RawLayout = Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]

_LAYOUT_MEMO = _fb.CappedMemo(64)


def layout_memo_stats() -> dict:
    return _LAYOUT_MEMO.stats()


def clear_layout_memo() -> None:
    """Reset the jax engine's layout caches (stage layouts and stacked
    bucket operands) with their counters."""
    _LAYOUT_MEMO.clear()
    _BUCKET_MEMO.clear()


def _raw_layouts(src: np.ndarray, dst: np.ndarray, vci: np.ndarray,
                 n_vcis: int, n_ranks: int,
                 key: Optional[Hashable]) -> Tuple[RawLayout, ...]:
    """Group the batch by each stage's resource id (memoized by ``key``).

    The layouts depend only on the (src, dst, vci) columns — which the
    memo key fully determines — never on times or sizes.
    """
    lays = _LAYOUT_MEMO.get(key)
    if lays is None:
        lays = (_group_layout(src * n_vcis + vci),
                _group_layout(src),
                _group_layout(src * n_ranks + dst))
        _LAYOUT_MEMO.put(key, lays)
    return lays


def _pad_layout(lay: RawLayout, n: int, sentinel: int,
                G: Optional[int] = None, K: Optional[int] = None):
    """Pad one stage's jagged groups to a fixed ``(K, G)`` matrix.

    The layout is *step-major* — row k holds the k-th message of every
    group — so ``lax.scan`` consumes it directly without a transpose.
    Returns ``(gather, mask, pos)``: ``gather[k, g]`` is the message id
    of the k-th message of group g (``sentinel`` — the shared dummy row —
    on padded slots), ``mask`` marks real slots, and ``pos[i]`` is the
    flattened padded position of message i, used to read per-message
    results back out of the scan output.
    """
    order, uniq, counts, offsets = lay
    Gi = len(counts)
    G = Gi if G is None else G
    K = (int(counts.max()) if Gi else 0) if K is None else K
    row = np.repeat(np.arange(Gi, dtype=np.int64), counts)
    col = np.arange(n, dtype=np.int64) - np.repeat(offsets, counts)
    gather = np.full((K, G), sentinel, dtype=np.int64)
    gather[col, row] = order
    mask = np.zeros((K, G), dtype=bool)
    mask[col, row] = True
    pos = np.empty(n, dtype=np.int64)
    pos[order] = col * G + row
    return gather, mask, pos


def _consts(cfg: NetConfig) -> Tuple[np.float64, ...]:
    """NetConfig costs as *dynamic* scalars.  Passing them as jit
    arguments (not trace-time constants) blocks XLA's
    divide-by-constant -> multiply-by-reciprocal rewrite, which would
    break the bit-for-bit contract under x64."""
    return tuple(np.float64(v) for v in (
        cfg.beta, cfg.beta_copy, cfg.alpha_wire, cfg.alpha_first,
        cfg.alpha_msg, cfg.chi_switch, cfg.alpha_nic, cfg.alpha_put,
        cfg.alpha_put_first, cfg.alpha_recv, cfg.eager_max, cfg.bcopy_max))


# ---------------------------------------------------------------------------
# The jitted pipeline
# ---------------------------------------------------------------------------

def _pipeline(t_ready, nbytes, thread, put, am_copy,
              g1, m1, pos1, cur1, prev1,
              g2, m2, pos2, cur2,
              g3, m3, pos3, cur3, consts):
    """Advance one padded batch through VCI -> NIC -> wire.

    Message columns carry one trailing dummy row (the gather target of
    padded slots).  Performs exactly the scalar engine's IEEE-754
    operations in the same per-resource order: scans are sequential
    within a resource's padded row and vectorized across rows.
    """
    (beta, beta_copy, alpha_wire, alpha_first, alpha_msg, chi_switch,
     alpha_nic, alpha_put, alpha_put_first, alpha_recv,
     eager_max, bcopy_max) = consts
    n = t_ready.shape[0] - 1  # trailing dummy row
    copy_sel = am_copy | ((nbytes > eager_max) & (nbytes <= bcopy_max))
    copy_cost = jnp.where(copy_sel, nbytes / beta_copy,
                          jnp.zeros_like(nbytes))
    zero = jnp.zeros_like(t_ready[:1])

    # Stage 1 — VCI banks: injection cost depends on the bank's previous
    # owner, so the scan carries (busy-until, last-thread).
    def vci_step(carry, x):
        cur, prev = carry
        rk, tk, pk, ck, mk = x
        base = jnp.where(
            prev < 0,
            jnp.where(pk, alpha_put_first, alpha_first),
            jnp.where(prev != tk, chi_switch,
                      jnp.where(pk, alpha_put, alpha_msg)))
        # adding 0.0 to non-copy rows is bitwise identity (as in the
        # NumPy engine's `cost + copy_cost`)
        t = jnp.maximum(rk, cur) + (base + ck)
        return (jnp.where(mk, t, cur), jnp.where(mk, tk, prev)), t

    (cur1, prev1), ys1 = lax.scan(
        vci_step, (cur1, prev1),
        (t_ready[g1], thread[g1], put[g1], copy_cost[g1], m1))
    t1 = jnp.concatenate([ys1.reshape(-1)[pos1], zero])

    # Stage 2 — per-rank NIC: constant service, then the rendezvous
    # RTS/CTS round trip for large non-AM messages (added after the
    # busy-until state, as in the scalar engine).
    def nic_step(cur, x):
        rk, mk = x
        t = jnp.maximum(rk, cur) + alpha_nic
        return jnp.where(mk, t, cur), t

    cur2, ys2 = lax.scan(nic_step, cur2, (t1[g2], m2))
    rdv = ~am_copy[:n] & (nbytes[:n] > bcopy_max)
    t2 = ys2.reshape(-1)[pos2] \
        + jnp.where(rdv, 2.0 * alpha_wire, jnp.zeros_like(zero[0]))
    t2 = jnp.concatenate([t2, zero])

    # Stage 3 — per-directed-link wires: bandwidth service time.
    wire_svc = nbytes / beta

    def wire_step(cur, x):
        rk, sk, mk = x
        t = jnp.maximum(rk, cur) + sk
        return jnp.where(mk, t, cur), t

    cur3, ys3 = lax.scan(wire_step, cur3, (t2[g3], wire_svc[g3], m3))
    t3 = ys3.reshape(-1)[pos3]
    return t3 + alpha_wire + alpha_recv, cur1, prev1, cur2, cur3


_JIT: dict = {}


def _jit_pipeline(grid: bool):
    """Build (once) the jitted single-batch or vmapped-grid pipeline."""
    _require_jax()
    fn = _JIT.get(grid)
    if fn is None:
        fn = jax.jit(jax.vmap(_pipeline) if grid else _pipeline)
        _JIT[grid] = fn
    return fn


def _pad_cols(t_ready, nbytes, thread, put, am_copy, n_pad: int):
    """Message columns padded to ``n_pad`` plus one trailing dummy row."""
    def pad(a, fill):
        out = np.full(n_pad + 1, fill, dtype=a.dtype)
        out[:a.shape[0]] = a
        return out
    return (pad(np.asarray(t_ready, dtype=np.float64), 0.0),
            pad(np.asarray(nbytes, dtype=np.float64), 0.0),
            pad(np.asarray(thread, dtype=np.int64), 0),
            pad(np.asarray(put, dtype=bool), False),
            pad(np.asarray(am_copy, dtype=bool), False))


def _pad_pos(pos: np.ndarray, n_pad: int) -> np.ndarray:
    out = np.zeros(n_pad, dtype=np.int64)
    out[:pos.shape[0]] = pos
    return out


class JaxFabric(Fabric):
    """Compiled fabric: the :class:`~repro.core.fabric.Fabric` resource
    model with the staged scans jitted through XLA.

    Scalar state stays authoritative on the Python side exactly as in
    the NumPy engine, so warm-state semantics (steady-state iterations,
    dependent RMA traffic interleaved with batches) are identical; a
    staged batch converts the touched resources' state to arrays, runs
    one jitted call, and writes the final clocks back.  Routing follows
    the same adaptive heuristics as the NumPy engine — tiny or narrow
    batches take the bit-identical scalar path, where jit dispatch
    could never pay for itself.
    """

    def __init__(self, cfg: NetConfig, n_vcis: int, n_ranks: int = 2):
        _require_jax()
        super().__init__(cfg, n_vcis, n_ranks=n_ranks)

    def transmit_arrays(self, t_ready, nbytes, vci, thread, put, am_copy,
                        src, dst, *, layout_key=None):
        n = t_ready.shape[0]
        if n == 0:
            return np.empty(0)
        per_src = np.bincount(src, minlength=self.n_ranks)
        if n <= _fb.SCALAR_BATCH_CUTOFF \
                or n < _fb.MIN_GROUP_PARALLELISM * int(per_src.max()):
            return self._transmit_scalar(t_ready, nbytes, vci, thread,
                                         put, am_copy, src, dst)
        vci = vci % self.n_vcis
        lay1, lay2, lay3 = _raw_layouts(src, dst, vci, self.n_vcis,
                                        self.n_ranks, layout_key)
        n_pad = _pow2(n)
        pads = []
        for lay in (lay1, lay2, lay3):
            Gi, Ki = len(lay[2]), int(lay[2].max())
            pads.append(_pad_layout(lay, n, n_pad,
                                    G=_pow2(Gi), K=_pow2(Ki)))
        (g1, m1, pos1), (g2, m2, pos2), (g3, m3, pos3) = pads

        # warm state in, padded to the quantized group counts (layouts
        # are step-major, so axis 1 is the group axis)
        banks = [(g // self.n_vcis, g % self.n_vcis)
                 for g in lay1[1].tolist()]
        cur1 = np.zeros(g1.shape[1])
        cur1[:len(banks)] = [self.vci_free[r][v] for r, v in banks]
        prev1 = np.full(g1.shape[1], -1, dtype=np.int64)
        prev1[:len(banks)] = [-1 if self.vci_last_thread[r][v] is None
                              else self.vci_last_thread[r][v]
                              for r, v in banks]
        ranks = lay2[1].tolist()
        cur2 = np.zeros(g2.shape[1])
        cur2[:len(ranks)] = [self.nic_free[r] for r in ranks]
        links = [(c // self.n_ranks, c % self.n_ranks)
                 for c in lay3[1].tolist()]
        cur3 = np.zeros(g3.shape[1])
        cur3[:len(links)] = [self.wire_free.get(sd, 0.0) for sd in links]

        cols = _pad_cols(t_ready, nbytes, thread, put, am_copy, n_pad)
        out = _jit_pipeline(grid=False)(
            *cols, g1, m1, _pad_pos(pos1, n_pad), cur1, prev1,
            g2, m2, _pad_pos(pos2, n_pad), cur2,
            g3, m3, _pad_pos(pos3, n_pad), cur3, _consts(self.cfg))
        arrivals = np.asarray(out[0], dtype=np.float64)
        cur1, cur2, cur3 = (np.asarray(out[i], dtype=np.float64)
                            for i in (1, 3, 4))
        prev1 = np.asarray(out[2])

        # warm state out
        for (r, v), busy, owner in zip(banks, cur1.tolist(),
                                       prev1.tolist()):
            self.vci_free[r][v] = busy
            self.vci_last_thread[r][v] = int(owner) if owner >= 0 else None
        for r, busy in zip(ranks, cur2.tolist()):
            self.nic_free[r] = busy
        self.wire_free.update(zip(links, cur3.tolist()))
        self.n_messages += n
        for r, c in enumerate(per_src.tolist()):
            if c:
                self.sent_per_rank[r] += c
        return arrivals[:n]


# ---------------------------------------------------------------------------
# The vmapped grid path
# ---------------------------------------------------------------------------

@dataclass
class GridItem:
    """One cold-start exchange of a whole-grid evaluation.

    Columns are already in global merge order (the caller's stable sort
    by ``t_ready``); ``key`` memoizes the stage layouts.  ``order[p]`` is
    the flow-major index of merge-ordered message p (None: the columns
    are in flow-major order already).  ``plan_key`` names the exchange's
    structure — its flow-major ``src``, ``dst``, ``vci`` columns and flows
    — independent of times: the pallas engine keeps the operands' plan
    of a structure under it across calls.
    """
    t_ready: np.ndarray
    nbytes: np.ndarray
    vci: np.ndarray
    thread: np.ndarray
    put: np.ndarray
    am_copy: np.ndarray
    src: np.ndarray
    dst: np.ndarray
    cfg: NetConfig
    n_vcis: int
    n_ranks: int
    key: Optional[Hashable] = None
    order: Optional[np.ndarray] = None
    plan_key: Optional[Hashable] = None

    def __len__(self) -> int:
        return self.t_ready.shape[0]


def transmit_grid(items: List[GridItem]) -> List[np.ndarray]:
    """Evaluate many independent cold-start exchanges in one vmapped jit.

    Items are bucketed by ``(n_ranks, n_vcis)`` (one rank-grid shape per
    bucket — the approach/theta/size axes ride the vmapped batch
    dimension), padded to the bucket's power-of-two maxima, and advanced
    by a single ``vmap``-ed pipeline call per bucket.  Returns each
    item's per-message arrival times in its input (merge) order.
    """
    _require_jax()
    out: List[Optional[np.ndarray]] = [None] * len(items)
    buckets: Dict[tuple, List[int]] = {}
    for i, it in enumerate(items):
        buckets.setdefault((it.n_ranks, it.n_vcis), []).append(i)
    # dispatch every bucket before syncing any: jax queues the jitted
    # calls asynchronously, so the buckets' XLA executions overlap the
    # host-side padding/stacking of their successors
    pending = []
    for members in buckets.values():
        pending.append((members, _dispatch_bucket(
            [items[i] for i in members])))
    for members, res in pending:
        arrivals = np.asarray(res[0], dtype=np.float64)
        for p, i in enumerate(members):
            out[i] = arrivals[p, :len(items[i])]
    return out  # type: ignore[return-value]


# Stacked padded tensors of a whole bucket, keyed by its members' layout
# keys: a repeated grid evaluation re-dispatches the jitted call on the
# cached tensors without re-padding anything.
_BUCKET_MEMO = _fb.CappedMemo(8)


def _stack_bucket(items: List[GridItem]) -> tuple:
    """Pad and stack one bucket's items into the vmapped jit's operands."""
    lays = [_raw_layouts(it.src, it.dst, it.vci % it.n_vcis, it.n_vcis,
                         it.n_ranks, it.key) for it in items]
    n_pad = _pow2(max(len(it) for it in items))
    dims = []  # per-stage (G, K) bucket maxima, quantized
    for s in range(3):
        G = _pow2(max(len(l[s][2]) for l in lays))
        K = _pow2(max(int(l[s][2].max()) for l in lays))
        dims.append((G, K))
    P = len(items)
    stacked_cols = [np.zeros((P, n_pad + 1), dtype=d)
                    for d in (np.float64, np.float64, np.int64, bool, bool)]
    stage = []
    for (G, K) in dims:
        stage.append((np.full((P, K, G), n_pad, dtype=np.int64),
                      np.zeros((P, K, G), dtype=bool),
                      np.zeros((P, n_pad), dtype=np.int64)))
    consts = np.empty((P, 12), dtype=np.float64)
    for p, (it, lay) in enumerate(zip(items, lays)):
        n = len(it)
        for c, col in zip(stacked_cols,
                          (it.t_ready, it.nbytes, it.thread,
                           it.put, it.am_copy)):
            c[p, :n] = col
        for s, (G, K) in enumerate(dims):
            g, m, pos = _pad_layout(lay[s], n, n_pad, G=G, K=K)
            stage[s][0][p] = g
            stage[s][1][p] = m
            stage[s][2][p, :n] = pos
        consts[p] = _consts(it.cfg)
    (g1, m1, pos1), (g2, m2, pos2), (g3, m3, pos3) = stage
    operands = (*stacked_cols, g1, m1, pos1,
                np.zeros((P, dims[0][0])),
                np.full((P, dims[0][0]), -1, dtype=np.int64),
                g2, m2, pos2, np.zeros((P, dims[1][0])),
                g3, m3, pos3, np.zeros((P, dims[2][0])),
                tuple(consts.T))
    # commit to device arrays once: cached buckets re-dispatch without
    # re-copying megabytes of padded tensors host->device every call
    return jax.tree_util.tree_map(jnp.asarray, operands)


def _dispatch_bucket(items: List[GridItem]):
    """Stack (or reuse) one bucket's operands and dispatch the jitted
    call; returns the *unsynced* jax result tuple."""
    key = None
    if all(it.key is not None for it in items):
        # precision mode keys the cache too: cached device arrays carry
        # the dtype they were created under
        key = (x64_enabled(), tuple(it.key for it in items))
    operands = _BUCKET_MEMO.get(key)
    if operands is None:
        operands = _stack_bucket(items)
        _BUCKET_MEMO.put(key, operands)
    return _jit_pipeline(grid=True)(*operands)
