"""Multi-device validation of partitioned-KV flash decode vs full-KV oracle."""
import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.flash_decode import flash_decode_ref, flash_decode_shard
from repro.compat import shard_map
from repro.launch.mesh import make_mesh

N = jax.device_count()
mesh = make_mesh((N,), ("x",))
B, H, KV, D, S = 2, 4, 2, 16, 64
key = jax.random.PRNGKey(0)
kq, kk, kv = jax.random.split(key, 3)
q = jax.random.normal(kq, (B, H, D), jnp.float32)
k = jax.random.normal(kk, (B, S, KV, D), jnp.float32)
v = jax.random.normal(kv, (B, S, KV, D), jnp.float32)

for pos, window, cap in [(S - 1, 0, None), (17, 0, None), (S - 1, 24, None),
                         (40, 16, 50.0)]:
    want = flash_decode_ref(q, k, v, pos=jnp.int32(pos), window=window,
                            attn_softcap=cap, scale=D ** -0.5)

    def f(q_, k_, v_):
        return flash_decode_shard(q_, k_, v_, axis="x",
                                  pos=jnp.int32(pos), window=window,
                                  attn_softcap=cap, scale=D ** -0.5)

    got = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(None, None, None), P(None, "x", None, None),
                  P(None, "x", None, None)),
        out_specs=P(None, None, None), check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5,
                               err_msg=f"pos={pos} window={window} cap={cap}")
    print(f"flash_decode pos={pos} window={window} cap={cap} ok")

print("ALL-OK")
