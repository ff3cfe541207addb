"""On-chip benchmark of REMOP's query path.

One command runs one cell (a configuration under a traffic mix) on the TPU:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, query shape,
metric or kernel sits in a file of its own under this directory and is found
by the name ``BENCHMARK.json`` gives it: ``configs/<config>.json``,
``workloads/<traffic>.json``, ``queries/<query>.py``,
``metrics/<metric>.py`` and ``kernels/<kernel>.py``.
"""
