"""Multi-device validation of the early-bird gradient-sync engine.

Checks, on a (4 data x 2 model) mesh:
  1. bulk / per_leaf / partitioned modes produce identical gradients
     (they differ only in collective placement, not math);
  2. grads equal the single-program data-parallel reference;
  3. HLO structure: partitioned mode emits its all-reduces INSIDE the
     backward scan (while loop), bulk emits none there;
  4. collective op counts: per_leaf >= partitioned >= bulk.
"""
import os
import re

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.earlybird import SyncConfig, value_and_synced_grad
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.compat import shard_map

jax.config.update("jax_threefry_partitionable", True)

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_smoke_config("llama3.2-1b")
params = lm.init_params(cfg, jax.random.PRNGKey(0))
B, S = 8, 32
key = jax.random.PRNGKey(1)
tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
batch = {"tokens": tokens, "labels": labels}

# reference: plain single-program grads on the full batch
ref_loss, ref_grads = jax.value_and_grad(
    lambda p: lm.loss_fn(cfg, p, batch))(params)


def make_step(mode, aggr=1 << 12):
    sync = SyncConfig(mode=mode, axes=("data",), aggr_bytes=aggr)

    def local_loss(p, bt, param_hooks):
        return lm.loss_fn(cfg, p, bt, param_hooks=param_hooks)

    vg = value_and_synced_grad(
        lambda p, bt, param_hooks=None: lm.loss_fn(cfg, p, bt,
                                                  param_hooks=param_hooks),
        sync)

    def step(p, bt):
        return vg(p, bt)

    return jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), {"tokens": P("data", None), "labels": P("data", None)}),
        out_specs=(P(), P()),
        check_vma=False, axis_names={"data"}))


results = {}
hlos = {}
pre_hlos = {}
for mode in ("bulk", "per_leaf", "partitioned"):
    step = make_step(mode)
    lowered = step.lower(params, batch)
    pre_hlos[mode] = lowered.as_text()        # pre-optimization structure
    hlos[mode] = lowered.compile().as_text()  # post-optimization placement
    loss, grads = step(params, batch)
    results[mode] = (loss, grads)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)

for mode, (loss, grads) in results.items():
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_flatten_with_path(ref_grads)[0][:10000],
            jax.tree_util.tree_flatten_with_path(grads)[0]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=f"{mode}: grad mismatch at {kb}")
print("grad equivalence ok")


def count_ar(txt):
    return len(re.findall(r"all-reduce(?:-start)?\(|stablehlo\.all_reduce",
                          txt))


def _hlo_computations(txt):
    out = {}
    cur_name, cur_lines = None, []
    for line in txt.splitlines():
        m = re.match(r"^(ENTRY\s+)?(%[\w\).\-\(]+|[\w.\-]+)\s*"
                     r"(?:\(.*\))?.*\{\s*$", line)
        if m and not line.startswith(" "):
            cur_name = m.group(2)
            cur_lines = []
            out[cur_name] = cur_lines
        elif cur_name is not None:
            cur_lines.append(line)
    return out


def ar_inside_while(txt):
    """Does any while-loop body computation contain an all-reduce?"""
    bl = _hlo_computations(txt)
    for lines in bl.values():
        body_txt = "\n".join(lines)
        for m in re.finditer(r"while\([^)]*\), condition=[%\w.\-]+, "
                             r"body=([%\w.\-]+)", body_txt):
            if "all-reduce" in "\n".join(bl.get(m.group(1), [])):
                return True
    return False


# Structural counts from the PRE-optimization module: XLA's all-reduce
# combiner later merges independent same-scope all-reduces (the compiler's
# own version of the paper's aggregation), which would mask the
# program-level distinction between the modes.
n_bulk = count_ar(pre_hlos["bulk"])
n_part = count_ar(pre_hlos["partitioned"])
n_leaf = count_ar(pre_hlos["per_leaf"])
print(f"all-reduce counts (pre-opt): bulk={n_bulk} partitioned={n_part} "
      f"per_leaf={n_leaf}")
assert n_bulk < n_part < n_leaf, (n_bulk, n_part, n_leaf)
assert n_bulk <= 3, n_bulk  # one fused gradient bucket (+ loss pmean)
n_leaves = len(jax.tree.leaves(params))
assert n_leaf >= n_leaves, (n_leaf, n_leaves)

# partitioned mode must place reductions inside the backward while loop
assert "while" in hlos["partitioned"]
assert ar_inside_while(hlos["partitioned"]), \
    "no all-reduce found inside scan body for partitioned mode"
assert not ar_inside_while(hlos["bulk"]), \
    "bulk mode unexpectedly has all-reduce inside scan body"
print("HLO placement ok")

print("ALL-OK")
