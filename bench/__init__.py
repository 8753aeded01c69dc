"""On-chip benchmark of the round engines.

One run measures one cell of ``BENCHMARK.json``:

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the configuration under
``bench/configs/``, the cell under ``bench/workloads/``, its query kind
under ``bench/queries/`` and each metric's reader under
``bench/metrics/``.  Generators, references, the trace reduction and the
peaks table live here too, so the yardstick does not move with the
program under test.
"""
