"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per run.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell once. Everything that belongs to one configuration,
traffic mix or per-layer metric is a file of its own, found by its name:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``
(or ``metrics/<prefix>.py`` for ``<prefix>.<suffix>``). The yardstick (data
generation, the plain reference and its comparison, work counts, peaks and
the trace reduction) lives here and imports nothing of the program.
"""
