"""Record the small TPU trace that ``test_trace.py`` reads.

    python -m chipbench.tests.record_trace <out.xplane.pb>

Run on a host with four TPU chips.  Inside a ``chipbench.window`` span
it runs ``STEPS`` steps, each of them: a matmul on every chip (its own
rows), then a sum of the results over the four chips alone in a program
of its own (so all of that exchange is exposed), then a host sleep of
``SLEEP_S`` inside a ``chipbench.sleep`` span, during which the chips
are idle.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time

STEPS = 4
SLEEP_S = 0.02


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench.trace import find_xplane
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < 4:
        print("needs four TPU chips", file=sys.stderr)
        return 3
    mesh = jax.sharding.Mesh(devs[:4], ("chips",))
    rows = NamedSharding(mesh, P("chips"))
    x = jax.device_put(jnp.ones((4 * 512, 512), jnp.bfloat16), rows)
    w = jax.device_put(jnp.ones((512, 512), jnp.bfloat16),
                       NamedSharding(mesh, P()))
    matmul = jax.jit(lambda a, b: a @ b, out_shardings=rows)
    exchange = jax.jit(jax.shard_map(
        lambda a: jax.lax.psum(a, "chips"), mesh=mesh, in_specs=P("chips"),
        out_specs=P("chips")))
    exchange(matmul(x, w)).block_until_ready()  # compile outside the trace

    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                exchange(matmul(x, w)).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.sleep"):
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    shutil.copy(find_xplane(tmp), out)
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
