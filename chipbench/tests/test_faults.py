"""Each cell's run, with the timed path broken underneath, comes out not
correct — once for each fault the cell can have.  The harness's look for
a chip is skipped; everything after it runs as on the chip, at a small
size on the CPU."""

from __future__ import annotations

import dataclasses

import pytest

from chipbench.tests import small


def test_fabric_sound_run_is_correct():
    out, checks = small.run(small.torus(), small.traffic("part-noise"))
    assert small.correct(out), checks


def test_fabric_altered_answer_is_not_correct(monkeypatch):
    from repro.core import simulator as sim
    real = sim.simulate_stencil_grid

    def altered(points, engine="jax"):
        res = real(points, engine=engine)
        r = res[0]
        tts = list(r.rank_tts_s)
        tts[len(tts) // 2] *= 1.0 + 1e-4  # one rank's time, where made
        return [dataclasses.replace(r, rank_tts_s=tts)]

    monkeypatch.setattr(sim, "simulate_stencil_grid", altered)
    out, checks = small.run(small.torus(), small.traffic("part-noise"))
    assert not small.correct(out)
    assert not checks["rank_tts_rel_err"].ok


def _patch_step(monkeypatch, wrap):
    from repro.launch import steps as S
    real = S.make_train_step

    def make(*a, **kw):
        step_fn, *rest = real(*a, **kw)
        return (wrap(step_fn), *rest)

    monkeypatch.setattr(S, "make_train_step", make)


TRAIN = {"train": 1, "train-dp4": 4}  # mix: chips


def _train(mix):
    return small.run(small.granite(), small.traffic(mix), chips=TRAIN[mix])


@pytest.mark.parametrize("mix", sorted(TRAIN))
def test_train_sound_run_is_correct(mix):
    out, checks = _train(mix)
    assert small.correct(out), checks


@pytest.mark.parametrize("mix", sorted(TRAIN))
def test_train_unchanged_state_is_not_correct(monkeypatch, mix):
    def frozen(step_fn):
        def step(state, batch):
            _, loss = step_fn(state, batch)
            return state, loss
        return step

    _patch_step(monkeypatch, frozen)
    out, checks = _train(mix)
    assert not small.correct(out)
    assert not checks["update_norm_gap"].ok


@pytest.mark.parametrize("mix", sorted(TRAIN))
def test_train_half_batch_is_not_correct(monkeypatch, mix):
    def half(step_fn):
        def step(state, batch):
            # the first half of the rows, repeated: the mean over them
            n = batch["tokens"].shape[0] // 2
            return step_fn(state, {k: jnp.concatenate([v[:n], v[:n]])
                                   for k, v in batch.items()})
        return step

    import jax.numpy as jnp
    _patch_step(monkeypatch, half)
    out, checks = _train(mix)
    assert not small.correct(out), checks


def test_train_without_the_exchange_is_not_correct(monkeypatch):
    from repro.core import earlybird
    monkeypatch.setattr(earlybird, "_bucketed_pmean",
                        lambda tree, sync, aggr_override=None: tree)
    out, checks = _train("train-dp4")
    assert not small.correct(out), checks

