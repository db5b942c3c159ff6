"""Each cell's control — the reference in the program's place, one
precision step below the configuration's — comes out not correct under
the cell's limits, while the program comes out correct, at a size the
CPU holds.  (The chip readings at the cells' own sizes are in PERF.md,
taken with ``python -m chipbench.readings``.)"""

from __future__ import annotations

import pytest

from chipbench import readings
from chipbench.tests import small

CASES = {  # mix: (configuration, chips)
    "part-noise": (lambda: small.torus((8, 8, 8)), 1),
    "train": (small.granite, 1),
    "train-dp4": (small.granite, 4),
}


@pytest.mark.parametrize("mix", sorted(CASES))
def test_control_fails_where_the_program_passes(mix):
    make, chips = CASES[mix]
    config, tr = make(), small.traffic(mix)
    out, checks = small.run(config, tr, seconds=0.3, seed=2**33 + 5,
                            chips=chips)
    assert small.correct(out), checks
    low = readings.CONTROLS[tr["kind"]](small.cell(config, tr, 0.3,
                                                   2**33 + 5, chips))
    failed = [k for k, v in low.items()
              if k in checks and not v <= checks[k].limit]
    assert failed, (low, {k: c.limit for k, c in checks.items()})
