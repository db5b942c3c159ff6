"""Readings that the limits of ``train_deepseek`` cells are set from.

    python -m chipbench.readings_deepseek --workload <name> --seeds 1,2 \
        --control-seeds 7,8,9 [--fault-seeds 7,8,9] [--seconds 3]

``chipbench.readings`` with this kind's control and planted faults, which
the ``train_deepseek`` driver defines: the control is the reference in
bfloat16 at default precision, each fault one of the reference's
``FAULTS`` (shared experts left out, the bias ignored in selection, the
dense layer routed, the loss over half of the step's tokens).  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import sys

from chipbench import readings
from chipbench.drivers import train_deepseek


def main(argv=None) -> int:
    generic = readings.faults

    def faults(cell):
        if cell.traffic["kind"] == "train_deepseek":
            return train_deepseek.faults(cell)
        return generic(cell)

    readings.CONTROLS["train_deepseek"] = train_deepseek.control
    readings.faults = faults
    return readings.main(argv)


if __name__ == "__main__":
    sys.exit(main())
