"""Record the small TPU trace that ``test_moonlight_cell.py`` reads.

    python -m chipbench.tests.record_scopes <out-prefix>

Run on a host with a TPU chip.  Compiles the program's train step for the
small Moonlight configuration (``small_moonlight.config``, 4 rows of 64
tokens), writes its scope map (``train_deepseek.scope_map`` of the
compiled step) to ``<out-prefix>.scopes.json``, and runs ``STEPS`` steps
inside a ``chipbench.window`` span under the profiler, whose trace goes
to ``<out-prefix>.xplane.pb``.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile

STEPS = 3


def main(prefix: str) -> int:
    from chipbench import harness
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    import jax.numpy as jnp

    from chipbench.drivers import lm_program, train_deepseek
    from chipbench.tests import small_moonlight
    from chipbench.trace import find_xplane
    from repro.launch import steps as S

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU chip", file=sys.stderr)
        return 3
    c = small_moonlight.config()
    ref = harness.reference(c)
    cfg = train_deepseek.model_config(c, "float32", 1.25)
    mesh = lm_program.mesh_for(1)
    with jax.set_mesh(mesh):
        step_fn, ss, bs, _ = S.make_train_step(
            cfg, mesh, S.StepConfig(param_dtype="float32",
                                    seq_parallel=False,
                                    capacity_factor=1.25),
            seq_len=64, global_batch=4)
        step = jax.jit(step_fn, donate_argnums=0).lower(ss, bs).compile()
        p = ref.init_params(c, jax.random.PRNGKey(0), {"*": "float32"})
        state = {"params": p,
                 "opt": {"step": jnp.int32(0),
                         "m": jax.tree.map(jnp.zeros_like, p),
                         "v": jax.tree.map(jnp.zeros_like, p)},
                 "router": ref.init_route_state(c)}
        state = jax.device_put(state, jax.tree.map(lambda s: s.sharding, ss))
        tok = jnp.ones((4, 64), jnp.int32)
        batch = jax.device_put({"tokens": tok, "labels": tok},
                               jax.tree.map(lambda s: s.sharding, bs))
        state, loss = step(state, batch)
        loss.block_until_ready()
        tmp = tempfile.mkdtemp()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(STEPS):
                state, loss = step(state, batch)
            loss.block_until_ready()
        jax.profiler.stop_trace()
    shutil.copy(find_xplane(tmp), prefix + ".xplane.pb")
    shutil.rmtree(tmp)
    with open(prefix + ".scopes.json", "w") as f:
        json.dump(train_deepseek.scope_map(step.as_text()), f, indent=0,
                  sort_keys=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
