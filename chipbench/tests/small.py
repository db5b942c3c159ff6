"""Small copies of the cells' configurations and traffic for CPU tests,
and a driver call that skips the harness's look for a chip."""

from __future__ import annotations

import time

from chipbench import harness


def torus(dims=(4, 4, 4)) -> dict:
    c = harness.load_json(harness.PKG / "configs" / "torus32k.json")
    c["dims"] = list(dims)
    return c


def granite() -> dict:
    c = harness.load_json(harness.PKG / "configs"
                          / "granite-moe-3b-a800m.json")
    c.update(hidden_size=256, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2,
             num_local_experts=8, num_experts_per_tok=2, vocab_size=2048,
             moe_dispatch_chunk=64, attention_multiplier=0.125)
    return c


def traffic(name: str, **over) -> dict:
    t = harness.load_json(harness.PKG / "traffic" / f"{name}.json")
    small = {"train": dict(seq_len=32, batch_per_chip=4),
             "train-dp4": dict(seq_len=32, batch_per_chip=2)}
    t.update(small.get(name, {}))
    t.update(over)
    return t


def cell(config: dict, traffic_: dict, seconds: float = 0.5,
         seed: int = 2**31 + 11, chips: int = 1) -> harness.Cell:
    import jax
    return harness.Cell(name="cpu-test", chips=chips, config=config,
                        traffic=traffic_, seed=seed, seconds=seconds,
                        trace=False, t_process=time.perf_counter(),
                        devices=jax.devices()[:chips])


def run(config: dict, traffic_: dict, seconds: float = 0.5,
        seed: int = 2**31 + 11, chips: int = 1):
    """The rest of a run, on the CPU: the driver and its checks."""
    out = harness.driver(traffic_["kind"]).run(
        cell(config, traffic_, seconds, seed, chips))
    return out, {c.name: c for c in out.checks}


def correct(out) -> bool:
    return all(c.ok for c in out.checks)
