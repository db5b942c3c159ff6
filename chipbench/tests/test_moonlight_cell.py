"""The ``moonlight.train-ep8`` cell's driver, faults, operation counts and
scope readers, at a small size on the CPU (``small_moonlight.config``);
nothing here is a device measurement.  The scope readers also read a
small trace recorded on a v5e (``record_scopes.py``)."""

from __future__ import annotations

import json
import math
from pathlib import Path

import pytest

from chipbench import flops_mla, harness, scopes, trace
from chipbench.drivers import lm_program, train_deepseek
from chipbench.tests import small, small_moonlight

REF = harness.load_module(harness.PKG / "configs" / "moonlight_ref.py")
RECORDED = Path(__file__).parent / "data" / "moonlight_small"
SCOPES = ("repro.mla", "repro.moe.route", "repro.moe.experts",
          "repro.moe.shared", "repro.mlp.dense")


def _traffic():
    return small.traffic("train-ep8", seq_len=32, batch_per_chip=4)


def _run():
    return small.run(small_moonlight.config(), _traffic())


def _ctx(out, trace_summary=None):
    return {"trace": trace_summary, "counters": out.counters,
            "peak": harness.peaks("TPU v5 lite"), "chips": 1}


def test_sound_run_is_correct_and_counts_its_routing():
    out, checks = _run()
    assert small.correct(out), checks
    assert set(checks) == {"loss_rel_gap", "update_norm_gap",
                           "route_bias_gap", "data_rows_mismatch",
                           "window_compiles", "loss_not_finite"}
    c = out.counters
    m = REF.dims(small_moonlight.config())
    assert len(c["load"]) == m["L"] and len(c["load"][0]) == m["E"]
    # every MoE layer routed all of the last step's (token, slot) pairs
    assert all(sum(row) == 4 * 32 * m["k"] for row in c["load"])
    assert set(SCOPES) <= set(c["scopes"].values())
    share = harness.metric_reader("moonlight.held_load_pct").read(_ctx(out))
    assert 0 < share < 100
    mfu = harness.metric_reader("moonlight.mfu_pct").read(_ctx(out))
    assert mfu > 0
    # without a trace the device readers have nothing to read
    for name in ("mla_ms", "routed_ms", "dense_ffn_ms", "unscoped_pct"):
        assert harness.metric_reader(f"moonlight.{name}").read(
            _ctx(out)) is None


@pytest.mark.parametrize("fault", (None,) + tuple(REF.FAULTS))
def test_control_and_planted_faults_read_not_correct(fault):
    """The bfloat16 control and each fault planted in the reference, in
    the program's place, break at least one limit."""
    cell = small.cell(small_moonlight.config(), _traffic())
    got = train_deepseek.control(cell, fault)
    lim = cell.traffic["limits"]
    assert any(v > lim[k] for k, v in got.items()), got


def _patch_step(monkeypatch, wrap):
    from repro.launch import steps as S
    real = S.make_train_step

    def make(*a, **kw):
        step_fn, *rest = real(*a, **kw)
        return (wrap(step_fn), *rest)

    monkeypatch.setattr(S, "make_train_step", make)


def test_unchanged_state_is_not_correct(monkeypatch):
    def frozen(step_fn):
        def step(state, batch):
            _, loss = step_fn(state, batch)
            return state, loss
        return step

    _patch_step(monkeypatch, frozen)
    out, checks = _run()
    assert not small.correct(out)
    assert not checks["update_norm_gap"].ok
    assert not checks["route_bias_gap"].ok


def test_program_ignoring_the_bias_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from repro.models import moe
    real = moe.select_sigmoid
    monkeypatch.setattr(moe, "select_sigmoid", lambda p, xt, mo, bias: real(
        p, xt, mo, jnp.zeros_like(bias)))
    out, checks = _run()
    assert not small.correct(out), checks


def test_program_without_shared_experts_is_not_correct(monkeypatch):
    import dataclasses
    real = train_deepseek.model_config

    def no_shared(*a, **kw):
        cfg = real(*a, **kw)
        return cfg.replace(moe=dataclasses.replace(cfg.moe, n_shared=0))

    monkeypatch.setattr(train_deepseek, "model_config", no_shared)
    with pytest.raises(ValueError, match="layout"):
        _run()


def test_train_flops_match_the_compiled_gradient():
    """``flops_mla`` against the compiler's matmul FLOPs at the sizes the
    program computes: every (query, key) pair under the causal mask and
    each held expert over its whole capacity buffer (at the sizes the
    work needs it reads less)."""
    import jax
    from repro.launch import steps as S
    from repro.launch.hlo_analysis import analyze_hlo
    B, T = 2, 32
    c = small_moonlight.config()
    cfg = train_deepseek.model_config(c, "float32", 1.25)
    mesh = lm_program.mesh_for(1)
    with jax.set_mesh(mesh):
        _, ss, bs, grad_fn = S.make_train_step(
            cfg, mesh, S.StepConfig(param_dtype="float32", remat=False,
                                    capacity_factor=1.25,
                                    seq_parallel=False),
            seq_len=T, global_batch=B)
        got = analyze_hlo(jax.jit(grad_fn).lower(
            ss["params"], bs, ss["router"]["bias"]).compile().as_text()
        ).dot_flops
    s = flops_mla.MLAShape.from_config(c)
    cap = cfg.moe.capacity(B * T)
    rows = s.moe_layers * s.held * cap
    # (the cross entropy's checkpointed head matmul is not recomputed
    # here: one loss chunk, and the untied head's forward product is
    # reused)
    program = 3 * flops_mla.forward_flops(s, B * T, B * T * T, rows, B * T)
    assert got == pytest.approx(program, rel=1e-6)
    kept = s.moe_layers * B * T * s.top_k * s.held // s.router_experts
    assert flops_mla.train_step_flops(s, B, T, kept) < program


def test_param_count_matches_the_program_layout():
    import jax
    from repro.models import lm
    c = small_moonlight.config()
    cfg = train_deepseek.model_config(c, "float32", 1.25)
    n = sum(x.size for x in jax.tree.leaves(lm.param_shapes(cfg)))
    assert flops_mla.MLAShape.from_config(c).param_count() == n
    full = harness.load_json(harness.PKG / "configs"
                             / "moonlight-16b-a3b.json")
    # the cell's share: 568.5 M parameters held (dense layer 82.97 M, 4
    # MoE layers of 100.40 M, embedding and head 83.89 M)
    assert flops_mla.MLAShape.from_config(full).param_count() == \
        pytest.approx(568.5e6, rel=2e-3)


def test_kept_rows_cap_each_held_expert():
    load = [[5, 9, 1, 7], [2, 2, 8, 0]]
    assert flops_mla.kept_rows(load, 1, 2, 6) == 6 + 1 + 2 + 6


def test_scope_map_takes_the_innermost_repro_scope():
    hlo = "\n".join([
        '  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(step)/repro.mla/dot_general" '
        'source_file="a.py" source_line=3}',
        '  ROOT %dot.2 = f32[4]{0} dot(%a, %b), metadata={op_name='
        '"jit(step)/transpose(jvp(repro.moe.route))/repro.moe.experts/'
        'dot_general"}',
        '  %copy.3 = f32[4]{0} copy(%a), metadata={op_name="jit(step)/add"}',
        '  %add.4 = f32[4]{0} add(%a, %b)',
    ])
    assert train_deepseek.scope_map(hlo) == {
        "%fusion.1": "repro.mla", "%dot.2": "repro.moe.experts"}


def test_scope_readers_on_a_synthetic_summary():
    s = trace.TraceSummary(window_s=1.0, n_devices=1, busy_s=0.6,
                           op_s={"%a fusion": 0.2, "%b dot": 0.3,
                                 "%c copy": 0.1},
                           module_s={}, module_n={})
    ctx = {"trace": s, "counters": {"train_steps": 2, "scopes": {
        "%a": "repro.mla", "%b": "repro.moe.experts"}}}
    assert scopes.ms_per_step(ctx, "repro.mla") == pytest.approx(100.0)
    assert harness.metric_reader("moonlight.routed_ms").read(ctx) == \
        pytest.approx(150.0)
    assert harness.metric_reader("moonlight.unscoped_pct").read(ctx) == \
        pytest.approx(100.0 * 0.1 / 0.6)


def test_scope_readers_on_a_recorded_trace():
    """A v5e trace of the small config's train step: every scope of the
    cell holds device time, and the scoped and unscoped parts add up to
    all the op time."""
    s = trace.reduce_trace(RECORDED.with_suffix(".xplane.pb"))
    names = json.loads(RECORDED.with_suffix(".scopes.json").read_text())
    ctx = {"trace": s, "counters": {"train_steps": 3, "scopes": names}}
    total = sum(s.op_s.values())
    scoped = scopes.seconds(ctx, *set(names.values()))
    assert scoped > 0
    for sc in SCOPES:
        assert scopes.seconds(ctx, sc) > 0, sc
    assert scoped / total + scopes.unscoped_share(ctx) == \
        pytest.approx(1.0, rel=1e-9)
    for name in ("mla_ms", "routed_ms", "dense_ffn_ms"):
        v = harness.metric_reader(f"moonlight.{name}").read(ctx)
        assert 0 < v < 1e3 * s.window_s / 3
    assert math.isfinite(
        harness.metric_reader("moonlight.unscoped_pct").read(ctx))
