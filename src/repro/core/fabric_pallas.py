"""The Pallas fabric engine: queue-scan kernels inside one jitted program.

Fourth engine of the fabric family (``engine="pallas"``).  It advances
the same three-stage resource model — per-rank VCI banks, per-rank NIC,
per-directed-link wires — as the jax engine, with each stage's queue
recurrence ``t[k] = max(r[k], t[k-1]) + c[k]`` run by a Pallas kernel:

  * the whole grid of sweep points is flattened into one cfg-bucketed
    super-batch; per-stage jagged groups are re-bucketed by segment
    depth — exact-depth buckets when a stage has at most
    :data:`MAX_EXACT_DEPTHS` distinct depths (the common stencil case:
    every VCI bank of a dimension sees the same message count), padded
    power-of-two classes otherwise;
  * per-message stage-1 costs (previous-owner injection chain, protocol
    copy costs) are precomputed on the host in float64 with exactly the
    scalar engine's operation order, so the kernels compute nothing but
    the queue recurrences;
  * each bucket is a ``(K, G)`` matrix — row k holds the k-th message of
    every segment, segments on the lanes — stored as ``(K, G/128, 128)``
    so one row fills whole vector registers.  G is padded to the lane
    tiling and K to the depth tiling; padded slots gather a release time
    of ``-inf`` and a cost of 0, so ``max(-inf, t) + 0 == t`` leaves the
    queue state untouched without any mask.  The kernel grid tiles G
    (independent segments, "parallel") and K (the recurrence, carried
    across steps in the resident ``last`` block, "arbitrary"), with
    blocks sized to fit v5e's default scoped VMEM;
  * the gathers between stage layouts and the finish reduction (per-flow
    max arrival + affine finish offsets + per-rank max) run in XLA
    around the kernels, in the same jitted program — a 32k-rank point
    returns 32768 floats instead of 1.6M arrivals;
  * each host stage of a grid call — assembly (``repro.fabric.operands``
    and its children), the copy to the device (``h2d``), ``launch`` and
    the wait for the result (``readback``) — is a profiler span
    (:mod:`repro.runtime.spans`).

Precision contract: the kernels compute in float32 on the chip (Mosaic
has no float64), tolerance-close to ``ReferenceFabric``.  Under
``JAX_ENABLE_X64`` on the CPU backend, where the kernels run in the
Pallas interpreter, the engine is bit-for-bit equal to
``ReferenceFabric`` (host costs are float64 with the reference
operation order; adding ``0.0`` is bitwise identity; ``max`` reductions
are order-independent).  x64 on any other backend raises.  Pinned by
``tests/test_engine_pallas.py``; ``tests/test_tpu_compile.py`` compiles
the kernels for a described v5e.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import fabric as _fb
from .fabric import NetConfig
from .fabric_jax import (HAVE_JAX, GridItem, JaxFabric, _consts,
                         _raw_layouts, _require_jax, x64_enabled)
from ..kernels import runtime as _rt
from ..runtime.spans import span

if HAVE_JAX:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

# A stage whose groups span at most this many distinct depths is
# bucketed by *exact* depth — no padding rows, no wasted sublanes.
MAX_EXACT_DEPTHS = 8
LANES = 128
# One grid step's double-buffered r, c and ys blocks (float32) stay
# within half of v5e's 16 MiB default scoped VMEM.
VMEM_BLOCK_BUDGET = 8 << 20
# Deepest K tile; deeper buckets carry the recurrence across grid steps.
MAX_TILE_DEPTH = 256


def _kernel_dtype():
    """float32 on the chip; float64 only under x64 on the CPU backend,
    where the interpreter keeps the bit-for-bit contract."""
    if not x64_enabled():
        return jnp.float32
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"engine='pallas' cannot run with JAX_ENABLE_X64 on the "
            f"{backend!r} backend: Mosaic kernels have no float64, so the "
            "engine's bit-for-bit x64 contract holds only in interpret mode"
            " on the CPU.  Disable x64 (float32 is tolerance-close) or use"
            " engine='jax'.")
    return jnp.float64


def _tiles(K: int, G: int) -> Tuple[int, int, int, int]:
    """``(TK, K_pad, TS, S_pad)`` of one ``(K, G)`` bucket: depth tile
    and padded depth, sublane-row tile and padded rows of 128 lanes."""
    nk = -(-K // MAX_TILE_DEPTH)
    TK = -(-K // nk)
    S = -(-G // LANES)
    ts_max = max(8, VMEM_BLOCK_BUDGET // (6 * TK * LANES * 4) // 8 * 8)
    if S <= ts_max:  # one block spans every row: no alignment needed
        return TK, TK * nk, S, S
    ns = -(-S // ts_max)
    TS = -(-(-(-S // ns)) // 8) * 8  # ceil(S / ns) rounded up to 8
    return TK, TK * nk, TS, TS * ns


@dataclass
class FinishSpec:
    """Finish reduction of one grid item, computed on the device.

    Valid only for *affine* finishes (``finish_batch(flows, None, x) ==
    x + foff`` elementwise — the caller probes this): the program then
    computes per-flow max arrival + ``foff`` and the per-rank max of
    those, returning per-rank completion times directly.
    """
    fid: np.ndarray    # (n,) flow id of each merge-ordered message
    foff: np.ndarray   # (F,) affine finish offset per flow
    fdst: np.ndarray   # (F,) destination rank per flow
    n_ranks: int


@dataclass
class _Bucket:
    """One depth-class of a stage: ``idx[k, g]`` is the id of the k-th
    member of the bucket's g-th segment, or the stage's sentinel on
    padded slots; ``sel`` names the segments as indices into the
    stage's concatenated group list; ``meta`` is ``(K_pad, S_pad, TK,
    TS, G, go)`` — padded shape, tiles, real segment count and the
    bucket's first segment in bucket-major group order."""
    idx: np.ndarray
    sel: np.ndarray
    meta: tuple


def _stage_buckets(order: np.ndarray, counts: np.ndarray,
                   offsets: np.ndarray, sentinel: int
                   ) -> Tuple[List[_Bucket], np.ndarray, int]:
    """Re-bucket one stage's jagged segments by depth class.

    Returns ``(buckets, pos, size)``: ``pos[i]`` is member i's slot in
    the stage's flat scan-output vector (concatenation of the buckets'
    raveled padded ``(K_pad, S_pad * 128)`` matrices, ``size`` total
    slots).  ``sentinel`` (the member count) fills padded slots.
    """
    exact = len(np.unique(counts)) <= MAX_EXACT_DEPTHS
    if exact:
        kcls = counts
    else:  # counts >= 1 always; log2 of an exact power of two is exact
        kcls = (1 << np.ceil(np.log2(np.maximum(counts, 1)))
                .astype(np.int64))
    pos = np.empty(sentinel, dtype=np.int64)
    buckets: List[_Bucket] = []
    base = go = 0
    for K in np.unique(kcls).tolist():
        sel = np.nonzero(kcls == K)[0]
        G = len(sel)
        TK, Kp, TS, S = _tiles(K, G)
        W = S * LANES
        cnt = counts[sel]
        offs = offsets[sel]
        total = int(cnt.sum())
        starts = np.zeros(G, dtype=np.int64)
        np.cumsum(cnt[:-1], out=starts[1:])
        within = np.arange(total, dtype=np.int64) - np.repeat(starts, cnt)
        col = np.repeat(np.arange(G, dtype=np.int64), cnt)
        members = order[np.repeat(offs, cnt) + within]
        idx = np.full((Kp, W), sentinel, dtype=np.int32)
        idx[within, col] = members
        pos[members] = base + within * W + col
        buckets.append(_Bucket(idx=idx, sel=sel,
                               meta=(Kp, S, TK, TS, G, go)))
        base += Kp * W
        go += G
    return buckets, pos, base


def _cost_columns(t_ready, nbytes, thread, put, am_copy, cfg: NetConfig,
                  lay1, warm_prev: Optional[np.ndarray]
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-message stage costs, precomputed host-side in float64.

    Performs exactly the scalar engine's IEEE-754 operations: the
    stage-1 injection cost needs each message's predecessor on its VCI
    bank — a pure function of the (memoized) bank grouping — so it
    vectorizes as a shifted gather instead of a scan.  ``warm_prev``
    seeds each bank's chain with its stored last owner (None = cold,
    every bank starts idle).  Returns ``(c1, c3, rdv)``: stage-1 cost
    (injection + protocol copy), stage-3 wire service time, and the
    rendezvous round-trip added to stage-3 release times.
    """
    n = t_ready.shape[0]
    nb = np.asarray(nbytes, dtype=np.float64)
    copy = am_copy | ((nb > cfg.eager_max) & (nb <= cfg.bcopy_max))
    copy_cost = np.where(copy, nb / cfg.beta_copy, 0.0)
    order1, _, _, offs1 = lay1
    th_s = np.asarray(thread)[order1]
    prev_s = np.empty_like(th_s)
    prev_s[offs1] = -1 if warm_prev is None else warm_prev
    inner = np.ones(n, dtype=bool)
    inner[offs1] = False
    prev_s[inner] = th_s[np.nonzero(inner)[0] - 1]
    put_s = np.asarray(put)[order1]
    base_s = np.where(
        prev_s < 0,
        np.where(put_s, cfg.alpha_put_first, cfg.alpha_first),
        np.where(prev_s != th_s, cfg.chi_switch,
                 np.where(put_s, cfg.alpha_put, cfg.alpha_msg)))
    c1 = np.empty(n)
    c1[order1] = base_s
    c1 = c1 + copy_cost  # += 0.0 on non-copy rows: bitwise identity
    rdv = np.where(~np.asarray(am_copy) & (nb > cfg.bcopy_max),
                   2.0 * cfg.alpha_wire, 0.0)
    c3 = nb / cfg.beta
    return c1, c3, rdv


def _stage_ops(lays, n: int):
    """The three scan stages' buckets and static operands, in the order
    :func:`_build_call` consumes them: per stage-1 bucket ``idx``, per
    stage-2 bucket ``pos1[idx]``, per stage-3 bucket ``idx, pos2[idx]``
    (each ``pos`` extended so a sentinel maps to the previous stage's
    sentinel slot).  Returns ``(core, statics, pos3, s3, grp_orders)``:
    the structure dict :func:`_runtime_meta` completes, the statics,
    message slots in the stage-3 output, its size, and each stage's
    bucket-major group permutation (for warm-state vectors)."""
    (b1, pos1, s1), (b2, pos2, s2), (b3, pos3, s3) = (
        _stage_buckets(lay[0], lay[2], lay[3], n) for lay in lays)
    pos1x = np.append(pos1, s1)
    pos2x = np.append(pos2, s2)
    statics: List[np.ndarray] = [bk.idx for bk in b1]
    statics += [pos1x[bk.idx].astype(np.int32) for bk in b2]
    for bk in b3:
        statics += [bk.idx, pos2x[bk.idx].astype(np.int32)]
    core = dict(n=n, st1=tuple(bk.meta for bk in b1),
                st2=tuple(bk.meta for bk in b2),
                st3=tuple(bk.meta for bk in b3), sizes=(s1, s2, s3),
                finf=(), n_flows=0, finr=())
    grp_orders = tuple(np.concatenate([bk.sel for bk in bks])
                       for bks in (b1, b2, b3))
    return core, statics, pos3, s3, grp_orders


@dataclass(frozen=True)
class _Meta:
    """Hashable shape/structure key of one program build (the
    ``lru_cache`` key of :func:`_build_call`): per-bucket ``_Bucket.meta``
    tuples plus the runtime switches that select a different trace."""
    mode: str           # "finish" | "arrivals"
    f64: bool
    interpret: bool
    n: int
    st1: tuple
    st2: tuple
    st3: tuple
    sizes: tuple        # flat scan-output slots per stage
    finf: tuple         # finish flow buckets
    n_flows: int
    finr: tuple         # finish rank buckets


@functools.lru_cache(maxsize=256)
def _scan_call(K: int, S: int, TK: int, TS: int, dtype_name: str,
               interpret: bool):
    """The queue-scan kernel of one ``(K, S, 128)`` bucket:
    ``(r, c, cur0) -> (ys, last)`` with ``ys[k] = max(r[k], ys[k-1]) +
    c[k]`` down the depth axis (``ys[-1] = cur0``) and ``last`` the
    carry after the final row."""
    dtype = jnp.dtype(dtype_name)

    def kernel(r_ref, c_ref, cur0_ref, ys_ref, last_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            last_ref[...] = cur0_ref[...]

        def row(k, cur):
            t = jnp.maximum(r_ref[k], cur) + c_ref[k]
            ys_ref[k] = t
            return t

        last_ref[...] = lax.fori_loop(0, TK, row, last_ref[...])

    blk = pl.BlockSpec((TK, TS, LANES), lambda j, k: (k, j, 0))
    rows = pl.BlockSpec((TS, LANES), lambda j, k: (j, 0))
    return pl.pallas_call(
        kernel, grid=(S // TS, K // TK),
        in_specs=[blk, blk, rows], out_specs=[blk, rows],
        out_shape=[jax.ShapeDtypeStruct((K, S, LANES), dtype),
                   jax.ShapeDtypeStruct((S, LANES), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="fabric_queue_scan")


@functools.lru_cache(maxsize=64)
def _build_call(meta: _Meta):
    """Build (once per structure) the jitted program advancing a whole
    super-batch: per stage, XLA gathers each bucket's release times and
    costs, the scan kernel advances it, and the flattened outputs feed
    the next stage's gathers.  Operand order mirrors :func:`_stage_ops`
    (then the finish statics of :func:`_assemble`); the NetConfig cost
    vector is a dynamic operand so cost points share the trace."""
    _require_jax()
    dtype = jnp.float64 if meta.f64 else jnp.float32
    finish = meta.mode == "finish"
    s1, s2, s3 = meta.sizes

    def scan_stage(buckets, init, operands):
        ys, lasts = [], []
        for (K, S, TK, TS, G, go), (r, c) in zip(buckets, operands):
            cur0 = jnp.pad(init[go:go + G], (0, S * LANES - G))
            y, last = _scan_call(K, S, TK, TS, jnp.dtype(dtype).name,
                                 meta.interpret)(
                r.reshape(K, S, LANES), c.reshape(K, S, LANES),
                cur0.reshape(S, LANES))
            ys.append(y.reshape(-1))
            lasts.append(last.reshape(-1)[:G])
        return jnp.concatenate(ys), jnp.concatenate(lasts)

    def bucket_max(v, G):
        return v.max(axis=0)[:G]

    def run(consts, tr, c1, c3, rdv, init1, init2, init3, *rest):
        it = iter(rest)
        foff = next(it) if finish else None
        aw, anic, ar = consts[2], consts[6], consts[9]
        ninf = jnp.full((1,), -jnp.inf, dtype)
        zero = jnp.zeros((1,), dtype)
        # message columns extended by the sentinel slot n
        tr_x = jnp.concatenate([tr, ninf])
        c1_x = jnp.concatenate([c1, zero])
        c3_x = jnp.concatenate([c3, zero])
        rdv_x = jnp.concatenate([rdv, zero])

        ops = []
        for _ in meta.st1:
            idx = next(it)
            ops.append((tr_x[idx], c1_x[idx]))
        ys1, cur1 = scan_stage(meta.st1, init1, ops)
        ys1 = jnp.concatenate([ys1, ninf])

        ops = []
        for _ in meta.st2:
            p = next(it)
            ops.append((ys1[p], jnp.where(p == s1, 0.0, anic)))
        ys2, cur2 = scan_stage(meta.st2, init2, ops)
        ys2 = jnp.concatenate([ys2, ninf])

        ops = []
        for _ in meta.st3:
            idx, p = next(it), next(it)
            # rendezvous RTS/CTS delays the wire-queue entry; the
            # carried busy-until state excludes the +aw+ar delivery
            # tail, which only the arrival values pick up
            ops.append((ys2[p] + rdv_x[idx], c3_x[idx]))
        ys3, cur3 = scan_stage(meta.st3, init3, ops)
        arr = ys3 + aw + ar

        if not finish:
            return arr[next(it)], cur1, cur2, cur3
        # arrivals > 0, so the sentinel's 0-fill never wins a max
        arr_x = jnp.concatenate([arr, zero])
        fmb = jnp.concatenate([bucket_max(arr_x[next(it)], G)
                               for (_, _, _, _, G, _) in meta.finf])
        fin = jnp.concatenate([fmb[next(it)] + foff, zero])
        return jnp.concatenate([bucket_max(fin[next(it)], G)
                                for (_, _, _, _, G, _) in meta.finr])

    return jax.jit(run)


def _runtime_meta(core: dict, mode: str) -> _Meta:
    return _Meta(mode=mode, f64=_kernel_dtype() == jnp.float64,
                 interpret=_rt.interpret_mode(), **core)


# ---------------------------------------------------------------------------
# Super-batch assembly (host side)
# ---------------------------------------------------------------------------

def _assemble(items: List[GridItem],
              finishes: Optional[List[FinishSpec]]):
    """Flatten one cfg-uniform bucket of grid items into the program's
    operands.  Per-item stage layouts (memoized, shared with the jax
    engine) compose by message-base offset — no global argsort; only the
    finish reduction's flow/rank groupings sort globally.  Returns
    ``(core, dyn, statics, aux)``: the structure dict :func:`_runtime_meta`
    completes, float64 dynamic operands, integer static operands, and
    the host-side unpack info.  Its stages are the spans
    ``repro.fabric.layouts``, ``cost_columns``, ``stage_ops`` and
    ``finish_layout``."""
    N = sum(len(it) for it in items)
    tr = np.empty(N)
    c1 = np.empty(N)
    c3 = np.empty(N)
    rdv = np.empty(N)
    st_lays = tuple(([], [], [], []) for _ in range(3))
    fid_l, foff_l, fdst_l, item_ranks = [], [], [], []
    item_lens = []
    base = fbase = rbase = 0
    for k, it in enumerate(items):
        n = len(it)
        sl = slice(base, base + n)
        with span("fabric.layouts"):
            lays = _raw_layouts(it.src, it.dst, it.vci % it.n_vcis,
                                it.n_vcis, it.n_ranks, it.key)
        tr[sl] = it.t_ready
        with span("fabric.cost_columns"):
            c1[sl], c3[sl], rdv[sl] = _cost_columns(
                it.t_ready, it.nbytes, it.thread, it.put, it.am_copy,
                it.cfg, lays[0], None)
        for s in range(3):
            o, u, cnt, f = lays[s]
            st_lays[s][0].append(o + base)
            st_lays[s][1].append(u)
            st_lays[s][2].append(cnt)
            st_lays[s][3].append(f + base)
        if finishes is not None:
            fin = finishes[k]
            fid_l.append(fin.fid + fbase)
            foff_l.append(fin.foff)
            fdst_l.append(fin.fdst + rbase)
            item_ranks.append((rbase, fin.n_ranks))
            fbase += len(fin.foff)
            rbase += fin.n_ranks
        item_lens.append(n)
        base += n
    with span("fabric.stage_ops"):
        lays = [tuple(np.concatenate(part) for part in lay)
                for lay in st_lays]
        core, statics, pos3, s3, _ = _stage_ops(lays, N)
    n_groups = [len(lay[2]) for lay in lays]
    dyn = [tr, c1, c3, rdv, np.zeros(n_groups[0]),
           np.zeros(n_groups[1]), np.zeros(n_groups[2])]
    aux: dict = {"item_lens": item_lens}
    if finishes is None:
        statics.append(pos3.astype(np.int32))
        return core, dyn, statics, aux
    with span("fabric.finish_layout"):
        fid = np.concatenate(fid_l)
        foff = np.concatenate(foff_l)
        fdst = np.concatenate(fdst_l)
        F = len(foff)
        of, uf, cf, ff = _fb._group_layout(fid)
        if len(uf) != F:
            raise ValueError("every flow needs at least one wire message")
        fbuckets, _, _ = _stage_buckets(of, cf, ff, N)
        pos3x = np.append(pos3, s3)
        fperm = np.empty(F, dtype=np.int32)
        for bk in fbuckets:
            statics.append(pos3x[bk.idx].astype(np.int32))
            G, go = bk.meta[4], bk.meta[5]
            fperm[uf[bk.sel]] = go + np.arange(G, dtype=np.int32)
        statics.append(fperm)
        orr, ur, cr, fr = _fb._group_layout(fdst)
        rbuckets, _, _ = _stage_buckets(orr, cr, fr, F)
        statics += [bk.idx for bk in rbuckets]  # flow ids: gathers from fin
        dyn.append(foff)
        aux.update(
            rank_out_ids=np.concatenate([ur[bk.sel] for bk in rbuckets]),
            item_ranks=item_ranks, n_ranks_total=rbase)
        core.update(finf=tuple(bk.meta for bk in fbuckets), n_flows=F,
                    finr=tuple(bk.meta for bk in rbuckets))
    return core, dyn, statics, aux


# Whole-super-batch operands (device-committed), keyed by the member
# items' layout keys + precision: benchmark repeats re-dispatch the
# program without re-assembling or re-copying anything.
_OPS_MEMO = _fb.CappedMemo(8)
# Single-batch arrivals-mode structure (stage buckets + static operands)
# for the warm-state driver path, keyed by layout key + precision.
_ARR_MEMO = _fb.CappedMemo(32)


def memo_stats() -> dict:
    return {"grid_ops": _OPS_MEMO.stats(), "arrivals": _ARR_MEMO.stats()}


def clear_memos() -> None:
    """Reset the pallas engine's operand caches and built programs with
    their counters (``sweep --profile`` cold pass)."""
    _OPS_MEMO.clear()
    _ARR_MEMO.clear()
    _build_call.cache_clear()
    _scan_call.cache_clear()


def _dispatch(items: List[GridItem],
              finishes: Optional[List[FinishSpec]]):
    """Assemble (or reuse) one bucket's operands and dispatch the
    program; returns the *unsynced* jax result plus the unpack aux.
    Its stages are the spans ``repro.fabric.operands`` (the assembly),
    ``h2d`` (the operands' copy to the device, ``bytes`` on it) and
    ``launch``."""
    mode = "finish" if finishes is not None else "arrivals"
    dtype = _kernel_dtype()
    key = None
    if all(it.key is not None for it in items):
        key = ("pallas-" + mode, x64_enabled(),
               tuple(it.key for it in items))
    entry = _OPS_MEMO.get(key) if key is not None else None
    if entry is None:
        with span("fabric.operands"):
            core, dyn, statics, aux = _assemble(items, finishes)
        with span("fabric.h2d") as sp:
            consts = jnp.asarray(np.array(_consts(items[0].cfg)), dtype)
            ops = ([consts] + [jnp.asarray(a, dtype) for a in dyn]
                   + [jnp.asarray(a) for a in statics])
            sp.set_metadata(bytes=sum(a.nbytes for a in ops))
        entry = (core, ops, aux)
        if key is not None:
            _OPS_MEMO.put(key, entry)
    core, ops, aux = entry
    with span("fabric.launch"):
        meta = _runtime_meta(core, mode)
        res = _build_call(meta)(*ops)
    return res, aux


def _cfg_buckets(items: List[GridItem]) -> Dict[tuple, List[int]]:
    """Items bucketed by (cfg, n_ranks, n_vcis): each bucket's NetConfig
    is uniform (one cost vector), and keeping rank-grid shapes uniform
    keeps each bucket's per-resource chain depths nearly uniform too —
    the exact-depth scan buckets stay under :data:`MAX_EXACT_DEPTHS`
    instead of fusing the whole sweep into one mixed-depth padded
    dispatch."""
    buckets: Dict[tuple, List[int]] = {}
    for i, it in enumerate(items):
        buckets.setdefault((it.cfg, it.n_ranks, it.n_vcis), []).append(i)
    return buckets


def transmit_grid(items: List[GridItem]) -> List[np.ndarray]:
    """Evaluate many independent cold-start exchanges through the scan
    kernels; returns each item's per-message arrival times in its input
    (merge) order.  Drop-in for :func:`repro.core.fabric_jax
    .transmit_grid` — used for points without an affine finish."""
    _require_jax()
    if not items:
        return []
    out: List[Optional[np.ndarray]] = [None] * len(items)
    pending = []
    for members in _cfg_buckets(items).values():
        res, aux = _dispatch([items[i] for i in members], None)
        pending.append((members, res, aux))
    for members, res, aux in pending:
        with span("fabric.readback"):
            arr = np.asarray(res[0], dtype=np.float64)
        o = 0
        for ln, i in zip(aux["item_lens"], members):
            out[i] = arr[o:o + ln]
            o += ln
    return out  # type: ignore[return-value]


def transmit_grid_finish(items: List[GridItem],
                         finishes: List[FinishSpec]) -> List[np.ndarray]:
    """Evaluate many cold-start exchanges *and their finish reductions*
    on the device; returns each item's per-rank completion times (ranks
    receiving no flow complete at 0.0, as in the host-side reduction).
    The 32k-rank path: device->host traffic shrinks from one float per
    wire message to one per rank."""
    _require_jax()
    if not items:
        return []
    out: List[Optional[np.ndarray]] = [None] * len(items)
    pending = []
    for members in _cfg_buckets(items).values():
        res, aux = _dispatch([items[i] for i in members],
                             [finishes[i] for i in members])
        pending.append((members, res, aux))
    for members, res, aux in pending:
        with span("fabric.readback"):
            rank_tts = np.asarray(res, dtype=np.float64)
        full = np.zeros(aux["n_ranks_total"])
        full[aux["rank_out_ids"]] = rank_tts
        for (rb, R), i in zip(aux["item_ranks"], members):
            out[i] = full[rb:rb + R]
    return out  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# The warm-state driver fabric
# ---------------------------------------------------------------------------

def _arr_structure(lays, n: int):
    """Stage buckets + committed static operands of one arrivals-mode
    batch (the warm driver path's per-layout structure cache entry)."""
    core, statics, pos3, _, grp_orders = _stage_ops(lays, n)
    statics.append(pos3.astype(np.int32))
    return core, [jnp.asarray(a) for a in statics], grp_orders


class PallasFabric(JaxFabric):
    """Kernel fabric: one jitted scan-kernel program per staged batch.

    Scalar state stays authoritative on the Python side exactly as in
    the jax engine — warm semantics (steady-state iterations, dependent
    RMA traffic between batches) are identical.  A staged batch folds
    the warm VCI owners into the host cost precompute, passes the
    per-resource busy-until clocks as the kernels' init vectors, and
    writes the carried-out clocks back.  Tiny or narrow batches take
    the same bit-identical scalar fallback as the other engines.
    """

    def __init__(self, cfg: NetConfig, n_vcis: int, n_ranks: int = 2):
        super().__init__(cfg, n_vcis, n_ranks=n_ranks)
        _kernel_dtype()  # fail at construction, not mid-simulation

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
        lays = _raw_layouts(src, dst, vci, self.n_vcis, self.n_ranks,
                            layout_key)
        skey = None
        if layout_key is not None:
            skey = ("pallas-arr", x64_enabled(), layout_key)
        entry = _ARR_MEMO.get(skey) if skey is not None else None
        if entry is None:
            entry = _arr_structure(lays, n)
            if skey is not None:
                _ARR_MEMO.put(skey, entry)
        core, statics, grp_orders = entry

        order1, uniq1, counts1, offs1 = lays[0]
        banks = [(g // self.n_vcis, g % self.n_vcis)
                 for g in uniq1.tolist()]
        warm_prev = np.array([-1 if self.vci_last_thread[r][v] is None
                              else self.vci_last_thread[r][v]
                              for r, v in banks], dtype=np.int64)
        c1, c3, rdv = _cost_columns(t_ready, nbytes, thread, put, am_copy,
                                    self.cfg, lays[0], warm_prev)
        state1 = np.array([self.vci_free[r][v] for r, v in banks])
        ranks = lays[1][1].tolist()
        state2 = np.array([self.nic_free[r] for r in ranks])
        links = [(c // self.n_ranks, c % self.n_ranks)
                 for c in lays[2][1].tolist()]
        state3 = np.array([self.wire_free.get(sd, 0.0) for sd in links])

        dtype = _kernel_dtype()
        dyn = [jnp.asarray(a, dtype) for a in
               (t_ready, c1, c3, rdv, state1[grp_orders[0]],
                state2[grp_orders[1]], state3[grp_orders[2]])]
        consts = jnp.asarray(np.array(_consts(self.cfg)), dtype)
        meta = _runtime_meta(core, "arrivals")
        arr, cur1, cur2, cur3 = _build_call(meta)(consts, *dyn, *statics)
        arrivals = np.asarray(arr, dtype=np.float64)

        # warm state out: the cur vectors are in bucket-group order;
        # unsort them back to each stage's group (resource) order
        s1o = np.empty(len(banks))
        s1o[grp_orders[0]] = np.asarray(cur1, dtype=np.float64)
        # a bank's final owner is its last queued message's thread — a
        # pure function of the (host-known) grouping, not of the times
        last_thread = np.asarray(thread)[order1[offs1 + counts1 - 1]]
        for (r, v), busy, owner in zip(banks, s1o.tolist(),
                                       last_thread.tolist()):
            self.vci_free[r][v] = busy
            self.vci_last_thread[r][v] = int(owner)
        s2o = np.empty(len(ranks))
        s2o[grp_orders[1]] = np.asarray(cur2, dtype=np.float64)
        for r, busy in zip(ranks, s2o.tolist()):
            self.nic_free[r] = busy
        s3o = np.empty(len(links))
        s3o[grp_orders[2]] = np.asarray(cur3, dtype=np.float64)
        self.wire_free.update(zip(links, s3o.tolist()))
        self.n_messages += n
        for r, cnt in enumerate(per_src.tolist()):
            if cnt:
                self.sent_per_rank[r] += cnt
        return arrivals
