"""Early-bird gradient synchronization — the paper's technique in JAX.

The MPI paper's pipelined pattern: each producer marks its partition ready
and communication starts immediately, overlapping the remaining compute
(Fig 2).  In data-parallel training the producers are *layers* in the
backward pass: layer L's gradient is complete while layers L-1..0 are still
computing.  We attach a custom-VJP identity to each layer's parameter slice
*inside* the scanned block, whose backward rule performs a bucketed
``pmean`` over the DP axes — so the per-layer all-reduces are emitted
inside the backward scan body, where XLA's collective pipeliner and
latency-hiding scheduler overlap them with the next layer's backward
compute.

Three modes mirror the paper's §2.3 taxonomy:

  * ``bulk``        — one fused collective for the whole gradient tree
                      after backward (the *Pt2Pt single* analogue: minimal
                      latency count, zero overlap).
  * ``per_leaf``    — one collective per parameter leaf (the *Pt2Pt many*
                      / no-aggregation partitioned analogue: maximal
                      overlap, maximal per-message latency — eq (5)).
  * ``partitioned`` — per-layer collectives, aggregated into buckets of at
                      most ``aggr_bytes`` (the paper's improved MPICH
                      implementation: aggregation + early-bird).

``compress='bf16'`` halves bytes on the wire (gradient compression); the
int8 ring variant lives in chunked_collectives.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .bucketing import bucketed_apply

Axes = Tuple[str, ...]

# the parameter keys of the scanned layer stacks (lm: "layers", and the
# leading dense layers' "prefix"), whose gradients the layer hooks sync
STACKS = ("layers", "prefix")


@dataclass(frozen=True)
class SyncConfig:
    mode: str = "partitioned"        # bulk | per_leaf | partitioned
    axes: Axes = ("data",)
    aggr_bytes: int = 4 << 20        # MPIR_CVAR_PART_AGGR_SIZE analogue
    comm_dtype: Optional[str] = None  # e.g. 'bfloat16' for compression
    n_channels: int = 1              # VCI analogue (structural tag)

    def __post_init__(self):
        assert self.mode in ("bulk", "per_leaf", "partitioned"), self.mode


def _constrain(tree, spec_tree):
    """with_sharding_constraint over the auto (TP) axes, if specs given.

    Inside a partial-auto shard_map, GSPMD does not propagate the params'
    'model' sharding into the backward accumulators — unconstrained
    cotangents materialize at FULL size (observed: 7 GiB f32 buffers for
    qwen2's stacked MLP grads).  Pinning each cotangent to its parameter's
    spec keeps the whole backward TP-sharded.
    """
    if spec_tree is None:
        return tree

    def pin(x, spec):
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, spec)

    return jax.tree.map(pin, tree, spec_tree,
                        is_leaf=lambda x: x is None or hasattr(x, "shape"))


def _pmean_flat(flat: jax.Array, axes: Axes) -> jax.Array:
    out = flat
    for ax in axes:
        out = jax.lax.pmean(out, ax)
    return out


def _bucketed_pmean(tree, sync: SyncConfig, aggr_override: Optional[int] = None):
    comm_dtype = jnp.dtype(sync.comm_dtype) if sync.comm_dtype else None
    aggr = sync.aggr_bytes if aggr_override is None else aggr_override

    def fn(flat, bucket):
        orig = flat.dtype
        if comm_dtype is not None:
            flat = flat.astype(comm_dtype)
        flat = _pmean_flat(flat, sync.axes)
        return flat.astype(orig)

    return bucketed_apply(tree, fn, aggr_bytes=aggr,
                          n_channels=sync.n_channels)


def auto_sync_config(params, *, axes: Axes = ("data",),
                     comm_dtype: Optional[str] = None,
                     tokens_per_step: float = 4096.0,
                     max_channels: int = 8,
                     workload=None, cfg=None) -> SyncConfig:
    """Model-chosen gradient-sync configuration (the autotuned analogue
    of hand-picking ``SyncConfig`` constants).

    Flattens ``params`` to measure the gradient payload, describes the
    backward pass as a :func:`repro.core.planner.training_workload` ramp
    (``tokens_per_step`` sets how much compute hides each gradient
    byte), and lets the planner search the (approach, aggregation,
    channels) space on a TPU-targeted NetConfig.  The chosen approach
    maps onto the paper's §2.3 taxonomy exactly as the modes do:
    ``pt2pt_single -> bulk``, ``pt2pt_many -> per_leaf``,
    ``part -> partitioned`` with the chosen bucket bound and channel
    count.
    """
    from . import planner

    from .bucketing import leaf_nbytes

    total = float(sum(leaf_nbytes(x) for x in jax.tree.leaves(params)))
    if workload is None:
        workload = planner.training_workload(2.0 * tokens_per_step)
    kw = {} if cfg is None else {"cfg": cfg}
    desc = planner.gradient_desc(total, workload=workload,
                                 max_channels=max_channels, **kw)
    choice = planner.choose_plan(desc)
    mode = {"pt2pt_single": "bulk", "pt2pt_many": "per_leaf",
            "part": "partitioned"}[choice.approach]
    aggr = int(choice.aggr_bytes) if mode == "partitioned" else \
        SyncConfig.aggr_bytes
    return SyncConfig(mode=mode, axes=axes, aggr_bytes=aggr,
                      comm_dtype=comm_dtype, n_channels=choice.n_vcis)


def make_layer_hook(sync: SyncConfig, layer_specs=None) -> Callable:
    """Hook wrapping each scanned layer's params (see lm.forward
    param_hooks).

    Identity on the forward pass; the backward rule pins the layer's
    cotangents to the parameter sharding (TP axes) and pmean-reduces the
    gradient buckets — the MPI_Pready moment of this layer.
    ``layer_specs``: pytree of per-layer-slice PartitionSpecs (leading L
    axis dropped) of one scanned stack.  Only active in 'partitioned' mode.
    """
    if sync.mode != "partitioned":
        return lambda lp: lp

    @jax.custom_vjp
    def hook(tree):
        return tree

    def fwd(tree):
        return tree, None

    def bwd(_, ct):
        ct = _constrain(ct, layer_specs)
        ct = _bucketed_pmean(ct, sync)
        return (_constrain(ct, layer_specs),)

    hook.defvjp(fwd, bwd)
    return hook


def finalize_grads(grads, sync: SyncConfig, *,
                   layers_keys: Tuple[str, ...] = STACKS,
                   param_specs=None):
    """Synchronize whatever the layer hooks did not.

    bulk:        everything, one bucket (aggr = inf).
    per_leaf:    everything, one collective per leaf (aggr = 0).
    partitioned: only the non-scanned params (embed/head/final_norm) —
                 the stacks' grads (``layers_keys``) were already reduced
                 inside the backward scans.
    """
    grads = _constrain(grads, param_specs)
    if sync.mode == "bulk":
        # "one message" semantically; capped bucket size bounds the packed
        # temp — XLA's all-reduce combiner fuses the rest into one stream.
        out = _bucketed_pmean(grads, sync, aggr_override=256 << 20)
    elif sync.mode == "per_leaf":
        out = _bucketed_pmean(grads, sync, aggr_override=0)
    else:
        rest = {k: v for k, v in grads.items() if k not in layers_keys}
        rest_specs = ({k: v for k, v in param_specs.items()
                       if k not in layers_keys} if param_specs else None)
        rest = _bucketed_pmean(rest, sync)
        rest = _constrain(rest, rest_specs)
        out = dict(grads)
        out.update(rest)
    return _constrain(out, param_specs)


def value_and_synced_grad(loss_fn: Callable, sync: SyncConfig,
                          *, has_aux: bool = False,
                          param_specs=None,
                          layers_keys: Tuple[str, ...] = STACKS
                          ) -> Callable:
    """jax.value_and_grad + the configured gradient synchronization.

    ``loss_fn(params, *args, param_hooks=...)`` must thread
    ``param_hooks``, one hook per stack of ``layers_keys``, into its
    scan bodies (repro.models.lm.loss_fn does).
    Must run inside shard_map with ``sync.axes`` as manual axes.
    ``param_specs``: full parameter PartitionSpec tree (TP axes) — used to
    pin gradient shardings inside the partial-auto shard_map.
    """
    def layer_specs(key):
        if param_specs is None or key not in param_specs:
            return None
        return jax.tree.map(
            lambda s: type(s)(*s[1:]) if s is not None else None,
            param_specs[key],
            is_leaf=lambda x: x is None or hasattr(x, "index"))

    hooks = {key: make_layer_hook(sync, layer_specs(key))
             for key in layers_keys}

    @functools.wraps(loss_fn)
    def wrapped(params, *args):
        f = lambda p: loss_fn(p, *args, param_hooks=hooks)
        if has_aux:
            (val, aux), grads = jax.value_and_grad(f, has_aux=True)(params)
        else:
            val, grads = jax.value_and_grad(f)(params)
            aux = None
        # cotangents through f32 ops (the CE head) come out f32; sync in
        # the parameter dtype — the wire format — and let the optimizer
        # re-upcast for accumulation.
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        grads = finalize_grads(grads, sync, layers_keys=layers_keys,
                               param_specs=param_specs)
        # the loss itself is cheap to sync; callers may also pmean it
        val = _pmean_flat(val, sync.axes)
        return ((val, aux), grads) if has_aux else (val, grads)

    return wrapped
