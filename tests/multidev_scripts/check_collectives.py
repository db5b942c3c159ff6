"""Multi-device validation of ring collectives vs jax.lax references."""
import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import chunked_collectives as cc
from repro.compat import shard_map
from repro.launch.mesh import make_mesh

N = jax.device_count()
assert N == 8, N
mesh = make_mesh((N,), ("x",))
key = jax.random.PRNGKey(0)


def smap(f, in_specs, out_specs):
    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


# --- ring_all_gather --------------------------------------------------------
x = jax.random.normal(key, (N * 4, 16))
for ch in (1, 2, 4):
    got = smap(lambda s: cc.ring_all_gather(s, "x", n_channels=ch,
                                            tiled=True),
               P("x", None), P(None, None))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), rtol=1e-6)
print("ring_all_gather ok")

# --- ring_reduce_scatter ----------------------------------------------------
y = jax.random.normal(key, (N, N, 4, 16))  # per-rank contributions


def rs(local):  # local: (N, 4, 16)
    return cc.ring_reduce_scatter(local, "x")


got = smap(rs, P("x", None, None), P("x", None))(
    y.reshape(N * N, 4, 16))
want = y.sum(axis=0).reshape(N * 4, 16)  # block i reduced over ranks
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)

for ch in (2, 4):
    got = smap(lambda l: cc.ring_reduce_scatter(l, "x", n_channels=ch),
               P("x", None, None), P("x", None))(
        y.reshape(N * N, 4, 16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
print("ring_reduce_scatter ok")

# --- ring_all_reduce --------------------------------------------------------
z = jax.random.normal(key, (N, 33, 7))  # deliberately awkward size


def ar(local):  # local: (33, 7) per rank
    return cc.ring_all_reduce(local, "x")


got = smap(ar, P("x", None), P("x", None))(z.reshape(N * 33, 7))
want = jnp.broadcast_to(z.sum(0), (N, 33, 7)).reshape(N * 33, 7)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
print("ring_all_reduce ok")

# --- ring_all_reduce_q8 (lossy) --------------------------------------------
got = smap(lambda l: cc.ring_all_reduce_q8(l, "x"),
           P("x", None), P("x", None))(z.reshape(N * 33, 7))
want_np = np.asarray(want)
err = np.abs(np.asarray(got) - want_np).max()
scale = np.abs(want_np).max()
assert err < 0.1 * scale, (err, scale)  # int8: ~1% per hop, 8 hops
print(f"ring_all_reduce_q8 ok (rel err {err/scale:.4f})")

# --- collective_ag_matmul ---------------------------------------------------
w = jax.random.normal(key, (16, 24))
xs = jax.random.normal(key, (N * 4, 16))
got = smap(lambda s, w_: cc.collective_ag_matmul(s, w_, "x"),
           (P("x", None), P(None, None)), P(None, None))(xs, w)
np.testing.assert_allclose(np.asarray(got), np.asarray(xs @ w), rtol=1e-4,
                           atol=1e-5)
print("collective_ag_matmul ok")

# --- collective_matmul_rs ---------------------------------------------------
xb = jax.random.normal(key, (N * 2, N * 16))   # (M, K) with K sharded
wb = jax.random.normal(key, (N * 16, 12))


def mmrs(x_full, w_shard):  # w_shard: (K/N, 12); x_full replicated
    return cc.collective_matmul_rs(x_full, w_shard, "x")


got = smap(mmrs, (P(None, "x"), P("x", None)), P("x", None))(xb, wb)
np.testing.assert_allclose(np.asarray(got), np.asarray(xb @ wb), rtol=1e-4,
                           atol=1e-4)
print("collective_matmul_rs ok")

print("ALL-OK")
