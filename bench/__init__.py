"""On-chip serving benchmark: one cell (configuration x traffic mix) per run.

Entry point: ``python bench/run.py --workload <cell> --seed <n> --seconds
<s> --trace <0|1>``; cells, configurations, traffic mixes and metrics
are data and small readers found by name (``bench/harness.py``).
"""
