"""The chip benchmark: cells of BENCHMARK.json, run one at a time.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own and is found by name:

* ``configs/<config>.json`` — sizes as run, source, cuts, deployment;
  ``reference`` names the plain reference beside it;
* ``traffic/<mix>.json`` — the mix's parameters; ``kind`` names the
  general driver in ``drivers/`` that reads them;
* ``metrics/<metric>.py`` — a reader ``read(ctx)`` of one per-layer
  metric from the trace summary and the driver's counters.

The yardstick lives here too: the table of peaks (``peaks.json``), the
operation and byte counts (``flops.py``) and the trace reduction
(``trace.py``).
"""
