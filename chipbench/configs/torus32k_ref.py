"""Plain reference of a partitioned stencil halo exchange on a torus.

A straightforward NumPy statement of the simulated semantics, written
from the model's description and not from the program: every rank sends
one face to each neighbour (``-1`` then ``+1`` along each dimension),
each face split into ``n_threads * theta`` partitions, one wire message
per partition (no aggregation), message ``m`` on VCI ``m % n_vcis``.

Per flow (one face of one rank), with ``start`` the thread barrier:

* partition ``(t, j)`` is marked ready at
  ``max(prev_t, start + ready[t, j]) + alpha_atomic``, then (several
  threads) serialised on the shared counter line: ``max(., bounce) +
  alpha_bounce``;
* message ``m`` may leave at ``max(pready[m], counter) + alpha_counter``
  (several threads), owned by thread ``m // theta``.

All messages of all flows are merged in order of that time (ties keep
flow order: rank, dimension, direction) and pass three serial queues,
each ``t = max(release, free) + cost``:

1. the sender's VCI: cost ``alpha_first`` when idle, ``chi_switch``
   when its last message came from another thread, else
   ``alpha_msg``; plus ``nbytes / beta_copy`` for bcopy sizes;
2. the sender's NIC: ``alpha_nic`` (then ``2 * alpha_wire`` for the
   rendezvous above ``bcopy_max``);
3. the directed link: ``nbytes / beta``.

A message arrives at ``wire + alpha_wire + alpha_recv``; a flow finishes
at its last arrival plus the thread barrier; a rank at the last of its
incoming flows.  ``time_s`` is the slowest rank less the compute time
(the ready table's maximum).

``dtype`` is the arithmetic's type: float64 for the reference, a
narrower type for the control.
"""

from __future__ import annotations

import math

import numpy as np


def barrier(net: dict, n_threads: int) -> float:
    if n_threads <= 1:
        return 0.0
    return net["barrier_base"] + net["barrier_log"] * math.log2(n_threads)


def face_bytes(c: dict) -> list:
    cells = math.prod(c["local_shape"])
    return [c["halo_width"] * cells // n * c["bytes_per_cell"]
            for n in c["local_shape"]]


def flows(dims) -> tuple:
    """(src, dst, dim) of every directed face exchange on a periodic
    torus, in (src, dim, direction) order."""
    dims = tuple(int(x) for x in dims)
    n = math.prod(dims)
    coords = np.stack(np.unravel_index(np.arange(n), dims), axis=1)
    src, dst, dim = [], [], []
    for d, size in enumerate(dims):
        if size == 1:
            continue
        for step in (-1, 1):
            c = coords.copy()
            c[:, d] = (c[:, d] + step) % size
            src.append(np.arange(n))
            dst.append(np.ravel_multi_index(tuple(c.T), dims))
            dim.append(np.full(n, d))
    # stack as (faces, ranks) and read rank-major: (src, dim, direction)
    return tuple(np.stack(a).T.ravel() for a in (src, dst, dim))


def flow_messages(c: dict, ready: np.ndarray, dtype):
    """(t_leave, thread, vci) of one flow's messages, in plan order."""
    net, nt, th = c["net"], c["n_threads"], c["theta"]
    f = dtype
    start = f(barrier(net, nt))
    pready = []
    bounce = f(0.0)
    for t in range(nt):
        free = start
        for j in range(th):
            done = f(max(free, start + f(ready[t, j])) + f(net["alpha_atomic"]))
            if nt > 1:
                done = f(max(done, bounce) + f(net["alpha_bounce"]))
                bounce = done
            pready.append(done)
            free = done
    leave, counter = [], f(0.0)
    for m in range(nt * th):
        t = pready[m]
        if nt > 1:
            t = f(max(t, counter) + f(net["alpha_counter"]))
            counter = t
        leave.append(t)
    m = np.arange(nt * th)
    return (np.array(leave, dtype=dtype), m // th, m % c["n_vcis"])


def queue(resource: np.ndarray, release: np.ndarray, cost: np.ndarray):
    """Completion times of one stage: messages in processing order, each
    ``max(release, previous completion on its resource) + cost``."""
    order = np.argsort(resource, kind="stable")
    res = resource[order]
    starts = np.flatnonzero(np.r_[True, res[1:] != res[:-1]])
    lens = np.diff(np.r_[starts, len(res)])
    depth = int(lens.max())
    rows = np.repeat(np.arange(len(starts)), lens)
    cols = np.arange(len(res)) - np.repeat(starts, lens)
    dt = release.dtype
    rel = np.full((len(starts), depth), -np.inf, dtype=dt)
    cst = np.zeros((len(starts), depth), dtype=dt)
    rel[rows, cols] = release[order]
    cst[rows, cols] = cost[order]
    out = np.empty_like(rel)
    cur = np.full(len(starts), -np.inf, dtype=dt)
    for k in range(depth):
        cur = np.maximum(rel[:, k], cur) + cst[:, k]
        out[:, k] = cur
    done = np.empty(len(res), dtype=dt)
    done[order] = out[rows, cols]
    return done


def simulate(c: dict, ready, dtype=np.float64) -> dict:
    """One point: ``rank_tts_s``, ``time_s`` and ``n_messages``."""
    if c["approach"] != "part" or c["aggr_bytes"] != 0.0:
        raise ValueError("the reference covers 'part' without aggregation")
    ready = np.asarray(ready, dtype=np.float64)
    net, f = c["net"], dtype
    src, dst, dim = flows(c["dims"])
    n_ranks = math.prod(c["dims"])
    n_part = c["n_threads"] * c["theta"]
    per_dim = []
    for fb in face_bytes(c):
        part = fb / n_part
        leave, thread, vci = flow_messages(c, ready, dtype)
        per_dim.append((leave, thread, vci, np.full(n_part, part)))
    M = n_part
    F = len(src)
    leave = np.stack([per_dim[d][0] for d in dim]).ravel()
    thread = np.stack([per_dim[d][1] for d in dim]).ravel()
    vci = np.stack([per_dim[d][2] for d in dim]).ravel()
    nbytes = np.stack([per_dim[d][3] for d in dim]).ravel()
    flow = np.repeat(np.arange(F), M)
    s_msg, d_msg = src[flow], dst[flow]

    # the merge: time order, ties in flow order
    merged = np.argsort(leave, kind="stable")
    leave, thread, vci, nbytes, flow, s_msg, d_msg = (
        a[merged] for a in (leave, thread, vci, nbytes, flow, s_msg, d_msg))

    # stage 1: VCI queues; the cost needs the VCI's previous owner
    bank = s_msg * c["n_vcis"] + vci
    order = np.argsort(bank, kind="stable")
    b_sorted = bank[order]
    first = np.r_[True, b_sorted[1:] != b_sorted[:-1]]
    th_sorted = thread[order]
    prev = np.r_[-1, th_sorted[:-1]]
    prev[first] = -1
    inject = np.where(prev < 0, net["alpha_first"],
                      np.where(prev != th_sorted, net["chi_switch"],
                               net["alpha_msg"]))
    cost1 = np.empty(len(bank))
    cost1[order] = inject
    copy = (nbytes > net["eager_max"]) & (nbytes <= net["bcopy_max"])
    cost1 = cost1 + np.where(copy, nbytes / net["beta_copy"], 0.0)
    t1 = queue(bank, leave.astype(f), cost1.astype(f))

    # stage 2: the sender's NIC, then the rendezvous round trip
    t2 = queue(s_msg, t1, np.full(len(t1), net["alpha_nic"], dtype=f))
    rdv = np.where(nbytes > net["bcopy_max"], 2.0 * net["alpha_wire"], 0.0)
    t2 = t2 + rdv.astype(f)

    # stage 3: the directed link
    link = s_msg * n_ranks + d_msg
    t3 = queue(link, t2, (nbytes / net["beta"]).astype(f))
    arrival = t3 + f(net["alpha_wire"]) + f(net["alpha_recv"])

    # maxima are exact in any precision: take them in float64
    flow_last = np.full(F, -np.inf)
    np.maximum.at(flow_last, flow, arrival.astype(np.float64))
    finish = (flow_last.astype(f) + f(barrier(net, c["n_threads"])))
    rank_tts = np.zeros(n_ranks)
    np.maximum.at(rank_tts, dst, finish.astype(np.float64))
    return {"rank_tts_s": rank_tts,
            "time_s": float(rank_tts.max()) - float(ready.max()),
            "n_messages": int(F * M)}
