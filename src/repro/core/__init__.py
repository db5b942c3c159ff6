"""Core: the paper's contribution (partitioned communication) for JAX/TPU.

  perfmodel            — closed-form gain/delay-rate model (paper §2.2, App A)
  planner              — model-driven CommPlan autotuner + closed-loop regret
  simulator            — schedule registry + multi-rank fabric + scenarios
  topology             — N-D Cartesian rank grids + per-dimension face payloads
  commplan             — THE plan layer: gcd agreement, aggregation, channels
  partition            — MPI-flavoured persistent-request view of commplan
  bucketing            — gradient-leaf aggregation (MPIR_CVAR_PART_AGGR_SIZE)
  earlybird            — per-layer in-backward bucketed gradient sync
  chunked_collectives  — multi-channel ring collectives + collective matmul
  flash_decode         — partitioned-KV decode attention with LSE combine
"""

from . import commplan, perfmodel, planner, simulator, topology  # noqa: F401
from .commplan import (CommPlan, WireMessage, channel_slices,  # noqa: F401
                       channel_streams, plan_auto, plan_sized, plan_uniform)
from .planner import (Candidate, GridEval, PlanChoice,  # noqa: F401
                      ScenarioDesc, choose_plan, evaluate_grid, rank_plans)
from .partition import (PartitionedRequest, agree_message_count,  # noqa: F401
                        aggregate_message_count)
from .topology import CartTopology, HaloSpec  # noqa: F401

# bucketing/earlybird pull in jax (~1s import); the simulator/sweep stack
# is pure NumPy, so those re-exports resolve lazily (PEP 562) to keep the
# CLI entry points fast.
_LAZY_EXPORTS = {
    "bucketing": ("bucketing", None),
    "earlybird": ("earlybird", None),
    "Bucket": ("bucketing", "Bucket"),
    "BucketPlan": ("bucketing", "BucketPlan"),
    "bucketed_apply": ("bucketing", "bucketed_apply"),
    "make_plan": ("bucketing", "make_plan"),
    "SyncConfig": ("earlybird", "SyncConfig"),
    "finalize_grads": ("earlybird", "finalize_grads"),
    "make_layer_hook": ("earlybird", "make_layer_hook"),
    "value_and_synced_grad": ("earlybird", "value_and_synced_grad"),
}


def __getattr__(name):
    target = _LAZY_EXPORTS.get(name)
    if target is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib
    module = importlib.import_module(f".{target[0]}", __name__)
    value = module if target[1] is None else getattr(module, target[1])
    globals()[name] = value  # cache: __getattr__ fires once per name
    return value
