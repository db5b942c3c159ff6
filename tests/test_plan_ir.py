"""Differential pass-equivalence suite for the CommPlan IR
(:mod:`repro.core.plan_ir`).

The IR's correctness story is differential end to end:

* **the anchor** — a freshly raised module reproduces its source driver
  bit-for-bit (``execute(raise_stencil(...))`` equals
  ``simulate_stencil`` on every engine; the faulty anchor equals
  ``simulate_faulty`` retransmission counters included), so the IR adds
  a representation, not a second simulator;
* **identity passes** — ``canonicalize`` (and the empty pipeline)
  lowers to bit-for-bit identical results on the vector *and* reference
  engines for hypothesis-generated multi-flow modules;
* **optimizing passes** — every rewrite (``fuse-faces``,
  ``merge-small-flows``, ``global-channels``) produces a module the
  engines still agree on exactly, and the guarded pipeline never
  returns a module with larger simulated total time, faults active or
  not — the "pipeline <= pointwise" property of the ``ir_passes``
  sweep records, held here by construction;
* **round-trip** — ``plan_of(raise_scenarios(...))`` equals
  ``sc.request().plan`` field for field for *every* schedule in the
  registry (RMA epochs included), while :func:`plan_ir.lower` rejects
  dependent-traffic schedules it cannot execute.

Engine invocation goes through the shared ``ir`` row of
``tests/_engines.DRIVERS``.
"""

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # env without hypothesis: deterministic fallback
    from _hypo import given, settings, st

from _engines import DRIVERS, assert_engines_agree, assert_results_equal
from repro.core import commplan as cp
from repro.core import plan_ir as pir
from repro.core import simulator as sim
from repro.core.fabric import DEFAULT_NET
from repro.core.faults import FaultSpec

ALL_SCHEDULES = sorted(sim.SCHEDULES)
PIPELINED = pir.PIPELINED
IR_FIELDS = DRIVERS["ir"].fields

STENCIL_KW = dict(dims=(2, 2), theta=4, n_threads=2, n_vcis=2,
                  local_shape=(24, 8))
FAULTY_KW = dict(dims=(2, 2), theta=4, face_bytes=(65536.0, 65536.0),
                 n_vcis=2)


def _random_scenarios(seed, n_flows, n_ranks=4, n_vcis=2):
    """A hypothesis-style multi-flow scenario list: mixed thread counts,
    plan shapes, aggregation bounds, start times and endpoints."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_flows):
        n = int(rng.choice([1, 2]))
        theta = int(rng.choice([1, 2, 4]))
        src = int(rng.integers(0, n_ranks))
        dst = int((src + 1 + rng.integers(0, n_ranks - 1)) % n_ranks)
        out.append(sim.Scenario(
            n_threads=n, theta=theta,
            part_bytes=float(rng.choice([256.0, 2048.0, 65536.0])),
            ready=rng.uniform(0.0, 25e-6, size=(n, theta)),
            n_vcis=n_vcis,
            aggr_bytes=float(rng.choice([0.0, 8192.0])),
            cfg=DEFAULT_NET, src=src, dst=dst,
            t0=float(rng.choice([0.0, 5e-6]))))
    return out


def _random_module(approach, seed, n_flows, n_ranks=4, n_vcis=2):
    return pir.raise_scenarios(
        approach, _random_scenarios(seed, n_flows, n_ranks, n_vcis),
        n_ranks=n_ranks, n_vcis=n_vcis)


# ---------------------------------------------------------------------------
# Round-trip: IR <-> CommPlan is lossless for every schedule
# ---------------------------------------------------------------------------

class TestRoundTrip:
    @pytest.mark.parametrize("approach", ALL_SCHEDULES)
    def test_plan_round_trip_every_schedule(self, approach):
        scs = _random_scenarios(seed=7, n_flows=5)
        mod = _random_module(approach, seed=7, n_flows=5)
        for fid, sc in enumerate(scs):
            assert pir.plan_of(mod, fid) == sc.request().plan

    def test_module_str_is_mlir_shaped(self):
        mod = _random_module("part", seed=0, n_flows=2)
        text = str(mod)
        assert text.startswith("module(approach = 'part'")
        for piece in ("%f0", "%f1", "partition_map", "channel_assign",
                      "barrier"):
            assert piece in text

    def test_barriers_raised_only_for_part(self):
        assert _random_module("part", 0, 2).barriers()
        assert not _random_module("pt2pt_many", 0, 2).barriers()


# ---------------------------------------------------------------------------
# The anchor: a raised module reproduces its source driver bit-for-bit
# ---------------------------------------------------------------------------

class TestDriverAnchor:
    @pytest.mark.parametrize("engine", ("vector", "reference"))
    @pytest.mark.parametrize("approach", PIPELINED)
    def test_raised_stencil_equals_driver(self, approach, engine):
        mod = pir.raise_stencil(approach, **STENCIL_KW)
        ir = pir.execute(mod, engine=engine)
        rv = sim.simulate_stencil(approach, engine=engine, **STENCIL_KW)
        assert ir.rank_tts_s == rv.rank_tts_s
        assert ir.tts_s == rv.tts_s and ir.time_s == rv.time_s
        assert ir.n_messages == rv.n_messages

    @pytest.mark.parametrize("engine", ("vector", "reference"))
    def test_raised_faulty_equals_driver(self, engine):
        spec = FaultSpec(drop_prob=0.05, seed=2)
        mod = pir.raise_stencil("part", **FAULTY_KW)
        ir = pir.execute(mod, engine=engine, faults=spec)
        rf = sim.simulate_faulty("part", faults=spec, engine=engine,
                                 **FAULTY_KW)
        assert ir.rank_tts_s == rf.rank_tts_s
        assert ir.tts_s == rf.tts_s
        assert ir.n_retransmits == rf.n_retransmits
        assert ir.retrans_bytes == rf.retrans_bytes
        assert ir.rounds == rf.rounds
        assert ir.n_messages == rf.n_messages

    @given(approach=st.sampled_from(PIPELINED),
           n_flows=st.sampled_from([1, 3, 6]), seed=st.integers(0, 4))
    @settings(max_examples=20, deadline=None)
    def test_engines_agree_on_raised_modules(self, approach, n_flows, seed):
        assert_engines_agree(
            "ir", approach, module=_random_module(approach, seed, n_flows))


# ---------------------------------------------------------------------------
# Identity passes: bit-for-bit on two engines
# ---------------------------------------------------------------------------

class TestIdentityPasses:
    @given(approach=st.sampled_from(PIPELINED),
           n_flows=st.sampled_from([2, 5]), seed=st.integers(0, 5))
    @settings(max_examples=20, deadline=None)
    def test_canonicalize_bit_for_bit(self, approach, n_flows, seed):
        mod = _random_module(approach, seed, n_flows)
        out = pir.Canonicalize().run(mod)
        for engine in ("vector", "reference"):
            assert_results_equal(
                pir.execute(mod, engine=engine),
                pir.execute(out, engine=engine), IR_FIELDS,
                context=f"canonicalize/{approach}/{engine}: ")

    def test_canonicalize_is_idempotent(self):
        mod = _random_module("part", seed=3, n_flows=4)
        once = pir.Canonicalize().run(mod)
        twice = pir.Canonicalize().run(once)
        assert once.ops == twice.ops

    def test_canonicalize_normalizes_structure(self):
        """Out-of-range channels reduce mod n_vcis and duplicate
        barriers collapse — without changing lowered columns."""
        base = _random_module("part", seed=1, n_flows=2)
        chans = base.channel_assigns()
        ops = []
        for op in base.ops:
            if isinstance(op, pir.ChannelAssignOp):
                ops.append(pir.ChannelAssignOp(
                    flow=op.flow,
                    channels=tuple(c + 2 * base.n_vcis
                                   for c in op.channels)))
            else:
                ops.append(op)
        ops.append(pir.BarrierOp(flow=0,
                                 n_threads=base.flows()[0].n_threads))
        noisy = pir.Module(approach=base.approach, n_ranks=base.n_ranks,
                           n_vcis=base.n_vcis, cfg=base.cfg,
                           ready_tables=base.ready_tables, ops=tuple(ops))
        noisy.validate()
        out = pir.Canonicalize().run(noisy)
        assert [op for op in out.ops
                if isinstance(op, pir.BarrierOp)] == list(
                    out.barriers().values())
        for fid, ch in out.channel_assigns().items():
            assert all(0 <= c < base.n_vcis for c in ch.channels)
            assert ch.channels == tuple(
                c % base.n_vcis for c in chans[fid].channels)
        assert_results_equal(pir.execute(noisy), pir.execute(out),
                             IR_FIELDS, context="canonicalize-noisy: ")

    @given(approach=st.sampled_from(PIPELINED), seed=st.integers(0, 3))
    @settings(max_examples=8, deadline=None)
    def test_empty_pipeline_is_identity(self, approach, seed):
        mod = _random_module(approach, seed, n_flows=3)
        pipe = pir.PassPipeline(passes=[])
        assert pipe.run(mod) is mod
        assert pipe.applied == []


# ---------------------------------------------------------------------------
# Optimizing passes: engines agree on rewrites; the guard never regresses
# ---------------------------------------------------------------------------

OPT_PASSES = (pir.FuseFaces, pir.MergeSmallFlows, pir.GlobalChannels)


class TestOptimizingPasses:
    @pytest.mark.parametrize("pass_cls", OPT_PASSES)
    @given(n_flows=st.sampled_from([2, 5]), seed=st.integers(0, 5))
    @settings(max_examples=12, deadline=None)
    def test_rewrite_equivalent_on_two_engines(self, pass_cls, n_flows,
                                               seed):
        """Every optimizing pass's output module is still executed
        identically by the vector and reference engines — a rewrite can
        change the plan, never the semantics of executing one."""
        mod = _random_module("part", seed, n_flows)
        out = pass_cls().run(mod)
        out.validate()
        assert_engines_agree("ir", "part", module=out)

    @given(n_flows=st.sampled_from([2, 5]), seed=st.integers(0, 5))
    @settings(max_examples=12, deadline=None)
    def test_guarded_pipeline_never_regresses(self, n_flows, seed):
        mod = _random_module("part", seed, n_flows)
        pipe = pir.default_pipeline()
        out = pipe.run(mod)
        assert pir.execute(out).tts_s <= pir.execute(mod).tts_s
        assert all(name in pir.PASSES for name in pipe.applied)

    @given(seed=st.integers(0, 3))
    @settings(max_examples=4, deadline=None)
    def test_guarded_pipeline_never_regresses_under_faults(self, seed):
        spec = FaultSpec(drop_prob=0.05, seed=seed)
        mod = pir.raise_stencil("part", **FAULTY_KW)
        out = pir.default_pipeline().run(mod, faults=spec)
        assert (pir.execute(out, faults=spec).tts_s
                <= pir.execute(mod, faults=spec).tts_s)

    def test_optimized_module_agrees_on_all_four_engines(self):
        """The acceptance bar: pass output runs unchanged through every
        fabric engine (x64 for the compiled one) with identical
        results."""
        pytest.importorskip("jax")
        from repro import compat
        mod = pir.raise_stencil("part", **STENCIL_KW)
        out = pir.default_pipeline().run(mod)
        with compat.x64_mode(True):
            assert_engines_agree(
                "ir", "part",
                engines=("vector", "reference", "pallas"),
                module=out)

    def test_passes_skip_non_partitioned_modules(self):
        mod = _random_module("pt2pt_many", seed=2, n_flows=3)
        for pass_cls in OPT_PASSES:
            assert pass_cls().run(mod) is mod


class TestPassStructure:
    """Deterministic structural checks of what each rewrite does."""

    def test_fuse_faces_merges_shared_links(self):
        """On a periodic size-2 torus both directions of a dimension
        land on the same neighbor: fuse-faces collapses the flow pairs
        and the fused module still executes identically everywhere."""
        mod = pir.raise_stencil("part", **STENCIL_KW)
        out = pir.FuseFaces().run(mod)
        assert len(out.flows()) < len(mod.flows())
        assert (sum(f.n_part for f in out.flows())
                == sum(f.n_part for f in mod.flows()))
        out.validate()
        assert_engines_agree("ir", "part", module=out)

    def test_merge_small_flows_coalesces_sub_bound_messages(self):
        sc = sim.Scenario(n_threads=1, theta=8, part_bytes=256.0,
                          ready=np.zeros((1, 8)), n_vcis=2,
                          cfg=DEFAULT_NET, src=0, dst=1)
        mod = pir.raise_scenarios("part", [sc], n_ranks=2, n_vcis=2)
        assert mod.n_wire == 8           # unaggregated pointwise plan
        out = pir.MergeSmallFlows(bound=8192.0).run(mod)
        assert out.n_wire == 1           # 8 x 256B fits one bcopy send
        assert_engines_agree("ir", "part", module=out)

    def test_global_channels_continues_round_robin_across_flows(self):
        scs = _random_scenarios(seed=0, n_flows=2, n_ranks=2)
        for sc in scs:
            object.__setattr__(sc, "src", 0)
            object.__setattr__(sc, "dst", 1)
        mod = pir.raise_scenarios("part", scs, n_ranks=2, n_vcis=2)
        out = pir.GlobalChannels().run(mod)
        seq = [c for fid in range(len(out.flows()))
               for c in out.channel_assigns()[fid].channels]
        assert seq == [m % 2 for m in range(len(seq))]
        assert_engines_agree("ir", "part", module=out)


# ---------------------------------------------------------------------------
# Validation and error paths
# ---------------------------------------------------------------------------

def _tiny_module(**overrides):
    """A minimal valid 1-flow partitioned module to mutate."""
    ready = (np.zeros((1, 2)),)
    ops = (pir.FlowOp(src=0, dst=1, n_threads=1, theta=2,
                      part_bytes=64.0, ready_class=0),
           pir.PartitionMapOp(flow=0, groups=((0,), (1,)),
                              nbytes=(64.0, 64.0)),
           pir.ChannelAssignOp(flow=0, channels=(0, 1)),
           pir.BarrierOp(flow=0, n_threads=1))
    kw = dict(approach="part", n_ranks=2, n_vcis=2,
              ready_tables=ready, ops=ops)
    kw.update(overrides)
    return pir.Module(**kw)


class TestValidation:
    def test_tiny_module_is_valid(self):
        _tiny_module().validate()

    @pytest.mark.parametrize("mutate,match", [
        (dict(approach="warp"), "unknown approach"),
        (dict(n_ranks=1), "endpoints outside"),
        (dict(ready_tables=()), "ready_class 0 unbound"),
        (dict(ready_tables=(np.zeros((2, 2)),)), "ready table shape"),
    ])
    def test_module_level_violations(self, mutate, match):
        with pytest.raises(ValueError, match=match):
            _tiny_module(**mutate).validate()

    @pytest.mark.parametrize("op,match", [
        (pir.PartitionMapOp(flow=0, groups=((0,),), nbytes=(64.0,)),
         "more than one PartitionMapOp"),
        (pir.ChannelAssignOp(flow=0, channels=(0,)),
         "more than one ChannelAssignOp"),
        (pir.PartitionMapOp(flow=5, groups=((0,),), nbytes=(64.0,)),
         "more than one|unknown flow"),
    ])
    def test_duplicate_and_dangling_ops(self, op, match):
        base = _tiny_module()
        mod = pir.Module(approach="part", n_ranks=2, n_vcis=2,
                         ready_tables=base.ready_tables,
                         ops=base.ops + (op,))
        with pytest.raises(ValueError, match=match):
            mod.validate()

    @pytest.mark.parametrize("pm_op,match", [
        (pir.PartitionMapOp(flow=0, groups=((0,),), nbytes=(64.0,)),
         "cover 0..1"),
        (pir.PartitionMapOp(flow=0, groups=((0, 0), (1,)),
                            nbytes=(128.0, 64.0)), "cover 0..1"),
        (pir.PartitionMapOp(flow=0, groups=((0,), (1,)),
                            nbytes=(64.0,)), "payload"),
    ])
    def test_partition_map_violations(self, pm_op, match):
        base = _tiny_module()
        ops = tuple(pm_op if isinstance(op, pir.PartitionMapOp) else op
                    for op in base.ops)
        with pytest.raises(ValueError, match=match):
            pir.Module(approach="part", n_ranks=2, n_vcis=2,
                       ready_tables=base.ready_tables,
                       ops=ops).validate()

    def test_channel_count_mismatch(self):
        base = _tiny_module()
        ops = tuple(pir.ChannelAssignOp(flow=0, channels=(0,))
                    if isinstance(op, pir.ChannelAssignOp) else op
                    for op in base.ops)
        with pytest.raises(ValueError, match="channels for"):
            pir.Module(approach="part", n_ranks=2, n_vcis=2,
                       ready_tables=base.ready_tables,
                       ops=ops).validate()

    def test_missing_plan_ops(self):
        with pytest.raises(ValueError, match="missing partition map"):
            _tiny_module(ops=_tiny_module().ops[:1]).validate()


class TestErrors:
    def test_lower_rejects_dependent_traffic(self):
        mod = _random_module("rma_many_passive", seed=0, n_flows=2)
        with pytest.raises(ValueError, match="dependent traffic"):
            pir.lower(mod)
        with pytest.raises(ValueError, match="dependent traffic"):
            pir.execute(mod)

    def test_raise_scenarios_rejects_unknown_approach(self):
        with pytest.raises(ValueError, match="unknown approach"):
            pir.raise_scenarios("warp", [], n_ranks=2, n_vcis=1)

    def test_serving_wave_rejects_single_stage(self):
        with pytest.raises(ValueError, match="n_stages"):
            pir.raise_serving_wave("part", rate_rps=1e3, n_requests=4,
                                   n_stages=1, theta=2, part_bytes=64.0)

    def test_dim_plans_conflicts_with_ready(self):
        with pytest.raises(ValueError, match="dim_plans"):
            pir.raise_stencil("part", dims=(2, 2), theta=2,
                              face_bytes=(256.0, 256.0),
                              ready=np.zeros((1, 2)),
                              dim_plans={0: (4, 0.0, 1)})

    def test_module_from_plan_rejects_ragged_split(self):
        plan = cp.plan_uniform(5, 5, 64.0)
        with pytest.raises(ValueError, match="split over"):
            pir.module_from_plan(plan, n_threads=2, part_bytes=64.0,
                                 n_vcis=1)


# ---------------------------------------------------------------------------
# The plan_auto hook
# ---------------------------------------------------------------------------

class TestPlanAutoHook:
    def test_pipeline_kwarg_runs_passes(self):
        pipe = pir.default_pipeline()
        base, _ = cp.plan_auto(64 * 256.0, n_threads=1, max_vcis=2)
        opt, _ = cp.plan_auto(64 * 256.0, n_threads=1, max_vcis=2,
                              pipeline=pipe)
        assert opt.n_items == base.n_items
        assert len(opt.messages) <= len(base.messages)
        covered = sorted(p for m in opt.messages for p in m.items)
        assert covered == list(range(opt.n_items))

    def test_pipeline_rejected_on_sizes_form(self):
        with pytest.raises(ValueError, match="uniform form"):
            cp.plan_auto(sizes=[512.0, 512.0],
                         pipeline=pir.default_pipeline())
