"""The fault-injection layer: seeded drops, degraded links, elastic
membership, and the planner's retransmission term.

Four contracts are pinned here:

* **the no-op gate** — a fault-free :class:`FaultSpec` run of
  ``simulate_faulty`` is bit-for-bit the healthy ``simulate_stencil``
  on *all three engines* (a ``factor == 1.0`` degradation window is
  likewise bitwise invisible: ``nbytes / (beta * 1.0)``);
* **engine independence under faults** — drop verdicts are pure
  functions of (flow-major message id, attempt) from the spec's
  ``SeedSequence``, so the vector engine (staged scans forced on
  included) equals the scalar oracle bit-for-bit with faults active,
  and the pallas engine's documented fallback equals vector;
* **the robustness claim** — at the committed sweep operating point the
  partitioned approach beats the bulk message on goodput-under-drops,
  and serving p99 inflates several-fold for bulk vs marginally for
  partitioned;
* **membership re-agreement** — a declared rank leave lands a finite
  quiesce + ``plan_mesh`` re-plan + warm-up bill on the measured clock.
"""

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # env without hypothesis: deterministic fallback
    from _hypo import given, settings, st

from _engines import assert_engines_agree
from repro.core import commplan, fabric as fb, planner as pl
from repro.core import simulator as sim
from repro.core.faults import (DropDraws, FaultSpec, LinkDegrade,
                               RankFailure, expected_retrans_s,
                               make_faulty_fabric)

PIPE_APPROACHES = ("pt2pt_single", "part", "pt2pt_many")
STENCIL_KW = dict(dims=(2, 2), theta=4, face_bytes=(65536.0, 65536.0),
                  n_vcis=2)


# ---------------------------------------------------------------------------
# Spec validation and primitives
# ---------------------------------------------------------------------------

class TestFaultSpec:
    @pytest.mark.parametrize("kw", [
        dict(drop_prob=1.0), dict(drop_prob=-0.1),
        dict(timeout_us=0.0), dict(backoff=0.5), dict(max_retries=0),
    ])
    def test_invalid_spec_raises(self, kw):
        with pytest.raises(ValueError):
            FaultSpec(**kw)

    @pytest.mark.parametrize("kw", [
        dict(t_start_us=0.0, t_end_us=1.0, factor=0.0),
        dict(t_start_us=0.0, t_end_us=1.0, factor=1.5),
        dict(t_start_us=2.0, t_end_us=1.0, factor=0.5),
    ])
    def test_invalid_degrade_raises(self, kw):
        with pytest.raises(ValueError):
            LinkDegrade(**kw)

    @pytest.mark.parametrize("kw", [
        dict(rank=-1, t_fail_us=1.0),
        dict(rank=0, t_fail_us=-1.0),
        dict(rank=0, t_fail_us=5.0, t_recover_us=5.0),
    ])
    def test_invalid_failure_raises(self, kw):
        with pytest.raises(ValueError):
            RankFailure(**kw)

    def test_noop_semantics(self):
        assert FaultSpec().is_noop
        # failures live above the fabric: the fabric itself stays healthy
        assert FaultSpec(failures=(RankFailure(0, 1.0),)).is_noop
        assert not FaultSpec(drop_prob=0.1).is_noop
        assert not FaultSpec(
            degradations=(LinkDegrade(0.0, 1.0, 0.5),)).is_noop
        assert not FaultSpec(drop_prob=0.1).is_noop

    def test_sequences_coerced_to_tuples(self):
        s = FaultSpec(degradations=[LinkDegrade(0.0, 1.0, 0.5)],
                      failures=[RankFailure(0, 1.0)])
        assert isinstance(s.degradations, tuple)
        assert isinstance(s.failures, tuple)

    def test_message_drop_prob_composes_per_partition(self):
        s = FaultSpec(drop_prob=0.1)
        assert s.message_drop_prob(1) == pytest.approx(0.1)
        assert s.message_drop_prob(2) == pytest.approx(1 - 0.9 ** 2)
        assert s.message_drop_prob(0) == 0.0  # 0-byte syncs immune
        np.testing.assert_allclose(
            s.message_drop_prob(np.array([0.0, 1.0, 8.0])),
            [0.0, 0.1, 1 - 0.9 ** 8])

    def test_wire_factor_scalar_equals_array(self):
        s = FaultSpec(degradations=(
            LinkDegrade(10.0, 20.0, 0.5, src=0, dst=1),
            LinkDegrade(15.0, 30.0, 0.25),           # wildcard overlap
        ))
        US = fb.US
        t = np.array([5.0, 10.0, 16.0, 20.0, 25.0, 30.0]) * US
        src = np.zeros(t.shape, dtype=np.int64)
        dst = np.ones(t.shape, dtype=np.int64)
        vec = s.wire_factor_array(src, dst, t)
        scal = [s.wire_factor(0, 1, float(x)) for x in t]
        assert vec.tolist() == scal  # bitwise: same ops, same order
        # window edges: start inclusive, end exclusive; overlap composes
        assert scal == [1.0, 0.5, 0.5 * 0.25, 0.25, 0.25, 1.0]
        # a non-matching link only sees the wildcard window
        assert s.wire_factor(1, 0, 16.0 * US) == 0.25


class TestDropDraws:
    def test_deterministic_and_extra_entropy(self):
        spec = FaultSpec(drop_prob=0.3, seed=11)
        a = DropDraws(spec, 64)
        b = DropDraws(spec, 64)
        c = DropDraws(spec, 64, extra=(1,))
        assert np.array_equal(a.u, b.u)
        assert not np.array_equal(a.u, c.u)

    def test_final_attempt_always_delivers(self):
        spec = FaultSpec(drop_prob=0.9, max_retries=3, seed=0)
        d = DropDraws(spec, 8)
        ids = np.arange(8)
        p = np.full(8, 0.999999)
        assert not d.dropped(ids, 3, p).any()
        assert d.dropped(ids, 0, p).all()


# ---------------------------------------------------------------------------
# The no-op gate: fault_rate=0 is bit-for-bit on all three engines
# ---------------------------------------------------------------------------

class TestNoopGate:
    @pytest.mark.parametrize("engine", sim.ENGINES)
    @pytest.mark.parametrize("approach", ("pt2pt_single", "part"))
    def test_empty_spec_reproduces_healthy_run(self, engine, approach):
        f = sim.simulate_faulty(approach, faults=FaultSpec(),
                                engine=engine, **STENCIL_KW)
        h = sim.simulate_stencil(approach, engine=engine, **STENCIL_KW)
        assert f.tts_s == h.tts_s            # bit-for-bit, no tolerance
        assert f.rank_tts_s == h.rank_tts_s
        assert f.n_messages == h.n_messages
        assert f.n_retransmits == 0 and f.rounds == 1
        assert f.clean_tts_s == f.tts_s and f.recovery_s == 0.0

    def test_none_spec_equals_empty_spec(self):
        a = sim.simulate_faulty("part", faults=None, **STENCIL_KW)
        b = sim.simulate_faulty("part", faults=FaultSpec(), **STENCIL_KW)
        assert a.tts_s == b.tts_s

    def test_factor_one_window_is_bitwise_invisible(self):
        # an *active* degradation path whose factor is 1.0 must still be
        # bitwise identical: nbytes / (beta * 1.0) == nbytes / beta
        spec = FaultSpec(degradations=(LinkDegrade(0.0, 1e6, 1.0),))
        assert not spec.is_noop
        for engine in ("vector", "reference"):
            f = sim.simulate_faulty("part", faults=spec, engine=engine,
                                    **STENCIL_KW)
            h = sim.simulate_stencil("part", engine=engine, **STENCIL_KW)
            assert f.tts_s == h.tts_s
            assert f.rank_tts_s == h.rank_tts_s


# ---------------------------------------------------------------------------
# Active drops: engine independence, reproducibility, the goodput win
# ---------------------------------------------------------------------------

class TestDrops:
    @given(approach=st.sampled_from(PIPE_APPROACHES),
           rate=st.sampled_from([0.01, 0.05, 0.2]),
           seed=st.integers(0, 3))
    @settings(max_examples=12, deadline=None)
    def test_vector_equals_reference_bit_for_bit(self, approach, rate, seed):
        assert_engines_agree(
            "faulty", approach, faults=FaultSpec(drop_prob=rate, seed=seed),
            **STENCIL_KW)

    def test_forced_staged_scans_stay_bit_for_bit(self):
        assert_engines_agree(
            "faulty", "part", forced=True,
            faults=FaultSpec(drop_prob=0.05, seed=2), **STENCIL_KW)

    @pytest.mark.parametrize("engine", ("pallas",))
    def test_compiled_engines_fall_back_to_vector(self, engine):
        spec = FaultSpec(drop_prob=0.05, seed=2)
        rv = sim.simulate_faulty("part", faults=spec, engine="vector",
                                 **STENCIL_KW)
        rc = sim.simulate_faulty("part", faults=spec, engine=engine,
                                 **STENCIL_KW)
        assert rc.tts_s == rv.tts_s
        assert rc.n_retransmits == rv.n_retransmits

    def test_seeded_reproducibility_and_seed_sensitivity(self):
        a = sim.simulate_faulty("part", faults=FaultSpec(drop_prob=0.1,
                                                         seed=5),
                                **STENCIL_KW)
        b = sim.simulate_faulty("part", faults=FaultSpec(drop_prob=0.1,
                                                         seed=5),
                                **STENCIL_KW)
        c = sim.simulate_faulty("part", faults=FaultSpec(drop_prob=0.1,
                                                         seed=6),
                                **STENCIL_KW)
        assert a.tts_s == b.tts_s and a.n_retransmits == b.n_retransmits
        assert (a.tts_s, a.n_retransmits) != (c.tts_s, c.n_retransmits)

    def test_drop_rate_monotone_under_shared_seed(self):
        # verdicts are u < p: raising p with the seed fixed can only add
        # drops, so retransmit count and completion are monotone
        prev_retx, prev_tts = -1, -1.0
        for rate in (0.01, 0.05, 0.2):
            r = sim.simulate_faulty("part",
                                    faults=FaultSpec(drop_prob=rate, seed=1),
                                    **STENCIL_KW)
            assert r.n_retransmits >= prev_retx
            assert r.tts_s >= prev_tts
            if r.n_retransmits:  # a lucky low-rate draw may drop nothing
                assert r.tts_s > r.clean_tts_s
            prev_retx, prev_tts = r.n_retransmits, r.tts_s
        assert prev_retx > 0  # the 20% point must actually drop

    def test_partitioned_beats_bulk_on_goodput_at_committed_point(self):
        # the faults sweep spec's operating point (fault_rate=0.05)
        kw = dict(dims=(4, 4), theta=8, face_bytes=(131072.0, 131072.0),
                  n_vcis=2)
        spec = FaultSpec(drop_prob=0.05, timeout_us=50.0, seed=3)
        bulk = sim.simulate_faulty("pt2pt_single", faults=spec, **kw)
        part = sim.simulate_faulty("part", faults=spec, **kw)
        assert part.goodput_bps > bulk.goodput_bps
        assert part.tts_s < bulk.tts_s
        # whole-buffer retransmits: bulk resends far more bytes per drop
        assert bulk.retrans_bytes / max(bulk.n_retransmits, 1) > \
            part.retrans_bytes / max(part.n_retransmits, 1)

    def test_degradation_window_slows_and_matches_reference(self):
        spec = FaultSpec(degradations=(LinkDegrade(0.0, 1e5, 0.25),))
        rv = sim.simulate_faulty("part", faults=spec, engine="vector",
                                 **STENCIL_KW)
        rr = sim.simulate_faulty("part", faults=spec, engine="reference",
                                 **STENCIL_KW)
        assert rv.tts_s == rr.tts_s
        assert rv.tts_s > rv.clean_tts_s
        assert rv.n_retransmits == 0 and rv.rounds == 1

    def test_dependent_traffic_rejects_drops(self):
        with pytest.raises(ValueError, match="pipelinable"):
            sim.simulate_faulty("rma_single_passive",
                                faults=FaultSpec(drop_prob=0.05),
                                **STENCIL_KW)
        # ... but degradation-only specs run the RMA schedule fine
        r = sim.simulate_faulty(
            "rma_single_passive",
            faults=FaultSpec(degradations=(LinkDegrade(0.0, 1e5, 0.5),)),
            **STENCIL_KW)
        assert r.tts_s >= r.clean_tts_s > 0.0

    def test_make_faulty_fabric_rejects_unknown_engine(self):
        with pytest.raises(ValueError, match="unknown engine"):
            make_faulty_fabric("cuda", fb.DEFAULT_NET, 1, 2, FaultSpec())


# ---------------------------------------------------------------------------
# Elastic membership
# ---------------------------------------------------------------------------

MEMBER_KW = dict(n_ranks=8, theta=8, part_bytes=16384.0, n_vcis=2,
                 n_iters=12, model_parallel=2)


class TestMembership:
    def test_leave_pays_finite_reagreement(self):
        spec = FaultSpec(failures=(RankFailure(3, t_fail_us=60.0),))
        r = sim.simulate_membership("part", faults=spec, **MEMBER_KW)
        assert r.n_events == 1
        assert len(r.epoch_starts) == 2 and r.epoch_starts[1] > 0
        assert np.isfinite(r.reagree_s) and r.reagree_s > 0.0
        assert r.quiesce_s > 0.0 and r.replan_s > 0.0
        assert r.warmup_s > 0.0      # cold fabric: measured, not modeled
        assert (r.plan_data, r.plan_model) == (3, 2)
        assert r.plan_dropped == 1   # 7 survivors at model=2 strands one
        assert len(r.iter_times_s) == r.n_iters
        # the re-agreement bill lands on the clock: total time exceeds
        # the sum of iteration times by at least the reagree cost
        assert r.tts_s > sum(r.iter_times_s) + r.reagree_s

    def test_rejoin_restores_plan_and_keeps_batch(self):
        spec = FaultSpec(failures=(
            RankFailure(3, t_fail_us=60.0, t_recover_us=180.0),))
        r = sim.simulate_membership("part", faults=spec, target_data=4,
                                    **MEMBER_KW)
        assert r.n_events == 2
        assert len(r.epoch_starts) == 3
        assert (r.plan_data, r.plan_dropped) == (4, 0)
        assert r.grad_accum_factor == 1  # back at full data parallelism

    def test_engine_independent(self):
        spec = FaultSpec(failures=(RankFailure(3, t_fail_us=60.0),))
        rv = sim.simulate_membership("part", faults=spec, engine="vector",
                                     **MEMBER_KW)
        rr = sim.simulate_membership("part", faults=spec,
                                     engine="reference", **MEMBER_KW)
        assert rv.tts_s == rr.tts_s
        assert rv.iter_times_s == rr.iter_times_s
        assert rv.n_messages == rr.n_messages

    def test_no_event_in_range_is_plain_steady_state(self):
        spec = FaultSpec(failures=(RankFailure(3, t_fail_us=1e6),))
        r = sim.simulate_membership("part", faults=spec, **MEMBER_KW)
        assert r.n_events == 0
        assert r.reagree_s == 0.0 and r.warmup_s == 0.0
        assert r.epoch_starts == [0]

    def test_validation_errors(self):
        with pytest.raises(ValueError, match="n_iters"):
            sim.simulate_membership("part", faults=None, n_ranks=4,
                                    theta=2, part_bytes=1024.0, n_iters=0)
        with pytest.raises(ValueError, match="at least 2"):
            sim.simulate_membership("part", faults=None, n_ranks=1,
                                    theta=2, part_bytes=1024.0, n_iters=2)
        # a leave that drops below the model-parallel floor must refuse
        spec = FaultSpec(failures=(RankFailure(1, t_fail_us=0.1),))
        with pytest.raises(ValueError, match="at least 2"):
            sim.simulate_membership("part", faults=spec, n_ranks=2,
                                    theta=2, part_bytes=1024.0, n_iters=4)


# ---------------------------------------------------------------------------
# Serving under drops
# ---------------------------------------------------------------------------

SERVE_KW = dict(arrival="bursty", rate_rps=14000.0, n_requests=64,
                n_tenants=4, n_stages=4, theta=8, part_bytes=131072.0,
                n_vcis=4, compute_us=40.0, window_us=5.0, seed=3)


class TestServingFaults:
    def test_drops_inflate_tail_and_stay_engine_independent(self):
        spec = FaultSpec(drop_prob=0.02, seed=2)
        fv = sim.simulate_serving("part", faults=spec, engine="vector",
                                  **SERVE_KW)
        fr = sim.simulate_serving("part", faults=spec, engine="reference",
                                  **SERVE_KW)
        clean = sim.simulate_serving("part", **SERVE_KW)
        assert fv.p99_s == fr.p99_s
        assert fv.n_retransmits == fr.n_retransmits > 0
        assert fv.retrans_bytes == fr.retrans_bytes > 0.0
        assert fv.p99_s > clean.p99_s
        assert np.array_equal(fv.latency_s, fr.latency_s)

    def test_empty_spec_is_noop_for_serving(self):
        f0 = sim.simulate_serving("part", faults=FaultSpec(), **SERVE_KW)
        clean = sim.simulate_serving("part", **SERVE_KW)
        assert f0.p99_s == clean.p99_s
        assert f0.n_retransmits == 0 and f0.retrans_bytes == 0.0
        assert np.array_equal(f0.latency_s, clean.latency_s)

    def test_bulk_tail_inflates_more_than_partitioned(self):
        spec = FaultSpec(drop_prob=0.02, seed=2)
        out = {}
        for ap in ("pt2pt_single", "part"):
            f = sim.simulate_serving(ap, faults=spec, **SERVE_KW)
            c = sim.simulate_serving(ap, **SERVE_KW)
            out[ap] = f.p99_s / c.p99_s
        assert out["pt2pt_single"] > out["part"]


# ---------------------------------------------------------------------------
# The planner's retransmission term
# ---------------------------------------------------------------------------

class TestPlannerFaults:
    DESC_KW = dict(total_bytes=float(1 << 22), n_threads=8)

    def test_no_faults_prediction_unchanged(self):
        cand = pl.Candidate("part", 8, 0.0, 4)
        base = pl.predict(pl.ScenarioDesc(**self.DESC_KW), cand)
        degr = pl.predict(
            pl.ScenarioDesc(faults=FaultSpec(
                degradations=(LinkDegrade(0.0, 1.0, 0.5),)),
                **self.DESC_KW), cand)
        assert base.predicted_s == degr.predicted_s
        assert dict(base.terms) == dict(degr.terms)
        assert "retrans" not in dict(base.terms)

    def test_drops_add_named_retrans_term(self):
        desc = pl.ScenarioDesc(faults=FaultSpec(drop_prob=0.05),
                               **self.DESC_KW)
        for ap, theta in (("pt2pt_single", 1), ("part", 8),
                          ("pt2pt_many", 8)):
            ch = pl.predict(desc, pl.Candidate(ap, theta, 0.0, 4))
            terms = dict(ch.terms)
            assert terms["retrans"] > 0.0
            assert sum(t for _, t in ch.terms) == pytest.approx(
                ch.predicted_s)
            base = pl.predict(pl.ScenarioDesc(**self.DESC_KW),
                              pl.Candidate(ap, theta, 0.0, 4))
            assert ch.predicted_s == pytest.approx(
                base.predicted_s + terms["retrans"])

    def test_aggregation_priced_out_under_drops(self):
        # a heavily aggregated plan retransmits group partitions per
        # drop; at 5% per-partition loss the model must charge it more
        desc = pl.ScenarioDesc(faults=FaultSpec(drop_prob=0.05),
                               **self.DESC_KW)
        fine = pl.predict(desc, pl.Candidate("part", 8, 0.0, 4))
        coarse = pl.predict(desc, pl.Candidate("part", 8, float(1 << 20), 4))
        assert dict(coarse.terms)["retrans"] > dict(fine.terms)["retrans"]

    def test_choice_shifts_away_from_bulk(self):
        healthy = pl.choose_plan(pl.ScenarioDesc(**self.DESC_KW),
                                 approaches=("pt2pt_single", "part"))
        faulty = pl.choose_plan(
            pl.ScenarioDesc(faults=FaultSpec(drop_prob=0.2),
                            **self.DESC_KW),
            approaches=("pt2pt_single", "part"))
        assert faulty.approach == "part"
        # ranking must place pt2pt_single strictly below the pick
        ranked = pl.rank_plans(
            pl.ScenarioDesc(faults=FaultSpec(drop_prob=0.2),
                            **self.DESC_KW),
            approaches=("pt2pt_single", "part"))
        bulk = [c for c in ranked if c.approach == "pt2pt_single"][0]
        assert bulk.predicted_s > faulty.predicted_s
        assert healthy.predicted_s <= faulty.predicted_s

    def test_signature_keeps_theta_for_bulk_under_drops(self):
        d0 = pl.ScenarioDesc(**self.DESC_KW)
        df = pl.ScenarioDesc(faults=FaultSpec(drop_prob=0.05),
                             **self.DESC_KW)
        a = pl.Candidate("pt2pt_single", 1, 0.0, 1)
        b = pl.Candidate("pt2pt_single", 8, 0.0, 1)
        assert pl._signature(d0, a) == pl._signature(d0, b)
        assert pl._signature(df, a) != pl._signature(df, b)

    def test_plan_auto_threads_faults(self):
        p0, c0 = commplan.plan_auto(float(1 << 22), n_threads=8)
        pf, cf = commplan.plan_auto(float(1 << 22), n_threads=8,
                                    faults=FaultSpec(drop_prob=0.05))
        assert "retrans" in dict(cf.terms)
        assert "retrans" not in dict(c0.terms)
        assert len(pf.messages) > 0

    def test_expected_retrans_properties(self):
        cfg = fb.DEFAULT_NET
        assert expected_retrans_s([(1024.0, 4, 2)], FaultSpec(), cfg) == 0.0
        lo = expected_retrans_s([(65536.0, 1, 8)],
                                FaultSpec(drop_prob=0.01), cfg)
        hi = expected_retrans_s([(65536.0, 1, 8)],
                                FaultSpec(drop_prob=0.1), cfg)
        assert 0.0 < lo < hi
        # more partitions per message -> likelier loss -> higher cost
        fine = expected_retrans_s([(65536.0, 1, 8)],
                                  FaultSpec(drop_prob=0.05), cfg)
        coarse = expected_retrans_s([(8 * 65536.0, 8, 1)],
                                    FaultSpec(drop_prob=0.05), cfg)
        assert coarse > fine
