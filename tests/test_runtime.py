"""Tier-1 coverage for the runtime layer: elastic re-planning and the
fault-tolerance primitives.

Regression anchors for the ``run_training_loop`` checkpoint-identity
bugs: the final synchronous save must stamp the last *completed* step
(never ``step + 1`` of a step that raised, never anything at all when
``num_steps == 0``) and must not duplicate a periodic save that already
covered the final step.  A recording fake checkpointer pins the exact
save sequence; the real async checkpointer is exercised in
``tests/test_substrates.py``.
"""

import json
import os
import signal
import time

import pytest

from repro.runtime import elastic
from repro.runtime.fault_tolerance import (Heartbeat, StragglerMonitor,
                                           run_training_loop)


class FakeCheckpointer:
    """Records every (step, state snapshot) save in call order."""

    def __init__(self):
        self.saves = []
        self.waits = 0

    def save_async(self, step, state, extra_meta=None):
        self.saves.append((step, dict(state)))

    def wait(self):
        self.waits += 1


def _counting_step(ceiling=None):
    """step_fn adding 1.0 to state["x"]; raises once x reaches ceiling."""
    def step_fn(state, batch):
        if ceiling is not None and state["x"] >= ceiling:
            raise RuntimeError("node failure")
        return {"x": state["x"] + 1.0}, state["x"]
    return step_fn


class TestPlanMesh:
    def test_exact_fit(self):
        p = elastic.plan_mesh(64, 8)
        assert (p.data, p.model) == (8, 8)
        assert p.dropped_devices == 0
        assert p.grad_accum_factor == 1
        assert p.n_devices == 64

    def test_dropped_devices(self):
        p = elastic.plan_mesh(67, 8)
        assert (p.data, p.model) == (8, 8)
        assert p.dropped_devices == 3

    def test_grad_accum_ceil(self):
        # 24 devices / model 8 -> data 3; keeping target_data=8 needs
        # ceil(8 / 3) = 3 micro-steps, not floor
        p = elastic.plan_mesh(24, 8, target_data=8)
        assert p.data == 3
        assert p.grad_accum_factor == 3

    def test_no_accum_when_data_meets_target(self):
        p = elastic.plan_mesh(64, 8, target_data=8)
        assert p.grad_accum_factor == 1

    def test_too_few_devices_raises(self):
        with pytest.raises(ValueError):
            elastic.plan_mesh(4, 8)

    def test_plan_mesh_importable_without_jax_side_effects(self):
        # the simulator's membership driver calls plan_mesh from the
        # NumPy engines; it must be pure arithmetic (no device queries)
        import inspect
        assert "jax" not in inspect.getsource(elastic.plan_mesh)


class TestBuildMeshAndReshard:
    """Single-device coverage of the device-touching half of elastic;
    the multi-device happy path runs in ``check_elastic.py``."""

    def test_build_mesh_rejects_oversized_plan(self):
        import jax
        plan = elastic.plan_mesh(8, 2)
        with pytest.raises(ValueError, match=r"re-plan with plan_mesh\(1, 2\)"):
            elastic.build_mesh(plan, devices=jax.devices()[:1])

    def test_build_mesh_single_device(self):
        plan = elastic.plan_mesh(1, 1)
        mesh = elastic.build_mesh(plan)
        assert mesh.shape == {"data": 1, "model": 1}

    def test_reshard_none_leaves_pass_through(self):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        mesh = elastic.build_mesh(elastic.plan_mesh(1, 1))
        tree = {"w": jnp.ones((4,)), "slot": None}
        out = elastic.reshard(tree, {"w": P(), "slot": P()}, mesh)
        assert out["slot"] is None
        assert float(out["w"].sum()) == 4.0

    def test_reshard_structure_mismatch_raises_named_error(self):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        mesh = elastic.build_mesh(elastic.plan_mesh(1, 1))
        tree = {"w": jnp.ones((4,)), "b": jnp.ones((2,))}
        with pytest.raises(ValueError, match="mismatched structure"):
            elastic.reshard(tree, {"w": P()}, mesh)


@pytest.mark.slow
def test_elastic_multidev(multidev):
    out = multidev("check_elastic.py")
    assert "elastic multidev OK" in out


class TestStragglerMonitor:
    def test_no_flag_below_min_samples(self):
        m = StragglerMonitor(window=50, threshold=2.0)
        for i in range(9):
            assert not m.record(i, 10.0 if i == 8 else 0.1)

    def test_window_eviction_shifts_median(self):
        m = StragglerMonitor(window=10, threshold=2.0)
        for i in range(10):
            m.record(i, 1.0)
        # 1.0-samples age out of the window: the median must follow
        for i in range(10, 30):
            m.record(i, 0.1)
        assert len(m.times) == 10
        assert m.median == pytest.approx(0.1)
        assert m.record(30, 0.3)  # 3x the *current* median
        assert m.straggler_steps == [30]


class TestHeartbeat:
    def test_stamps_on_enter(self, tmp_path):
        """A fresh rank must look live immediately, not after the first
        full interval (the watchdog-flags-fresh-ranks regression)."""
        path = tmp_path / "hb.json"
        with Heartbeat(path, interval=60.0):
            doc = json.loads(path.read_text())  # no sleep: enter stamped
            assert doc["step"] == 0
            assert doc["pid"] == os.getpid()

    def test_creates_missing_parent_directory(self, tmp_path):
        """A fresh checkpoint directory: the heartbeat makes its parent
        instead of failing on the first stamp."""
        path = tmp_path / "ckpt" / "arch" / "heartbeat.json"
        with Heartbeat(path, interval=60.0):
            assert json.loads(path.read_text())["step"] == 0

    def test_background_stamp_carries_updated_step(self, tmp_path):
        path = tmp_path / "hb.json"
        with Heartbeat(path, interval=0.02) as hb:
            hb.update(5)
            deadline = time.time() + 5.0
            while time.time() < deadline:
                if json.loads(path.read_text())["step"] == 5:
                    break
                time.sleep(0.01)
            assert json.loads(path.read_text())["step"] == 5


class TestTrainingLoop:
    def test_zero_steps_saves_nothing(self):
        ck = FakeCheckpointer()
        rep = run_training_loop(step_fn=_counting_step(), state={"x": 0.0},
                                start_step=5, num_steps=0,
                                checkpoint_every=3, checkpointer=ck,
                                get_batch=lambda s: s)
        assert rep.steps_run == 0
        assert rep.final_step == 5  # not 6: step 5 never ran
        assert ck.saves == []

    def test_exception_saves_last_completed_step(self):
        # steps 5, 6, 7 complete (x: 0->3), step 8 raises mid-step
        ck = FakeCheckpointer()
        with pytest.raises(RuntimeError):
            run_training_loop(step_fn=_counting_step(ceiling=3.0),
                              state={"x": 0.0}, start_step=5, num_steps=10,
                              checkpoint_every=0, checkpointer=ck,
                              get_batch=lambda s: s)
        assert ck.saves == [(8, {"x": 3.0})]  # completed id, matching state

    def test_final_save_dedupes_periodic(self):
        # num_steps=6 with checkpoint_every=3: periodic saves at 3 and 6,
        # and 6 is already the final step -> no duplicate synchronous save
        ck = FakeCheckpointer()
        rep = run_training_loop(step_fn=_counting_step(), state={"x": 0.0},
                                start_step=0, num_steps=6,
                                checkpoint_every=3, checkpointer=ck,
                                get_batch=lambda s: s)
        assert rep.final_step == 6
        assert [s for s, _ in ck.saves] == [3, 6]

    def test_final_save_added_when_periodic_missed_it(self):
        ck = FakeCheckpointer()
        rep = run_training_loop(step_fn=_counting_step(), state={"x": 0.0},
                                start_step=0, num_steps=7,
                                checkpoint_every=3, checkpointer=ck,
                                get_batch=lambda s: s)
        assert rep.final_step == 7
        assert [s for s, _ in ck.saves] == [3, 6, 7]
        assert ck.saves[-1][1] == {"x": 7.0}

    def test_preemption_guard_save_and_exit(self):
        """SIGTERM mid-loop: finish the in-flight step, save it, report
        preempted — and restore the original signal handlers."""
        orig = signal.getsignal(signal.SIGTERM)
        ck = FakeCheckpointer()

        def step_fn(state, batch):
            if state["x"] == 2.0:  # third step: request preemption
                os.kill(os.getpid(), signal.SIGTERM)
            return {"x": state["x"] + 1.0}, state["x"]

        rep = run_training_loop(step_fn=step_fn, state={"x": 0.0},
                                start_step=0, num_steps=100,
                                checkpoint_every=0, checkpointer=ck,
                                get_batch=lambda s: s)
        assert rep.preempted
        assert rep.steps_run == 3
        assert ck.saves == [(3, {"x": 3.0})]
        assert signal.getsignal(signal.SIGTERM) is orig
