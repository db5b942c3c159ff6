"""The operation counts against the matmul FLOPs the compiler counts in
the program's own compiled steps (``launch.hlo_analysis``), at a small
size.  The program computes more than the work needs — every expert
over its whole capacity buffer, every (query, key) pair under the
causal mask — so the comparison feeds those sizes to the same
functions; at the sizes the work needs they read less."""

from __future__ import annotations

import math

import pytest

from chipbench import flops
from chipbench.drivers import lm_program
from chipbench.tests import small


def _dot_flops(fn, *args) -> float:
    import jax
    from repro.launch.hlo_analysis import analyze_hlo
    return analyze_hlo(jax.jit(fn).lower(*args).compile().as_text()).dot_flops


def _setup(cf):
    import jax
    from repro.launch import steps as S
    c = small.granite()
    cfg = lm_program.model_config(c, "float32", cf)
    mesh = lm_program.mesh_for(1)
    return c, cfg, mesh, S, jax


def _cap(c, tokens, cf):
    cap = math.ceil(tokens * c["num_experts_per_tok"]
                    / c["num_local_experts"] * cf)
    return max(c["moe_min_capacity"], cap)


def test_train_flops_match_the_compiled_gradient():
    B, T = 2, 32
    c, cfg, mesh, S, jax = _setup(1.25)
    s = flops.LMShape.from_config(c)
    with jax.set_mesh(mesh):
        _, ss, bs, grad_fn = S.make_train_step(
            cfg, mesh, S.StepConfig(param_dtype="float32", remat=False,
                                    capacity_factor=1.25),
            seq_len=T, global_batch=B)
        got = _dot_flops(grad_fn, ss["params"], bs)
    e_rows = c["num_local_experts"] * _cap(c, B * T, 1.25)
    # the chunked cross entropy recomputes its head matmul (checkpoint)
    head = 2 * B * T * s.d * s.vocab
    program = 3 * flops.forward_flops(s, B * T, B * T * T, B * T,
                                      expert_rows=e_rows) + head
    assert got == pytest.approx(program, rel=1e-6)
    assert flops.train_step_flops(s, B, T) < program


def test_param_count_matches_the_program_layout():
    import jax
    c, cfg, *_ = _setup(1.25)
    from repro.models import lm
    n = sum(x.size for x in jax.tree.leaves(lm.param_shapes(cfg)))
    assert flops.LMShape.from_config(c).param_count() == n


def test_fabric_bytes_count_columns_in_and_ranks_out():
    assert flops.fabric_point_bytes(10, 2) == 10 * 16 + 2 * 4
    peak = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 2.0}
    assert flops.least_time(4.0, 2.0, peak) == 4.0
    assert flops.least_time(1.0, 8.0, peak) == 4.0
