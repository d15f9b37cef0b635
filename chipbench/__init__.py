"""On-chip benchmark of the latency predictor (see BENCHMARK.json).

One run of one cell: ``python3 chipbench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.  Everything a cell needs is
found by name: its configuration in ``configs/``, its traffic mix in
``traffic/``, and each metric's reader in ``metrics/``.
"""
