"""PI's chip benchmark: YCSB cells through collect -> WAL -> dispatch.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that defines the yardstick lives here, apart from the program:
the traffic generator (``ycsb``), the plain reference (``reference``), the
correctness comparison (``check``), the trace reduction (``trace``), the
peaks table and byte counts (``roofline``), and one reader per metric
(``metrics/<name>.py``).  Configurations (``configs/``) and traffic mixes
(``traffic/``) are data files found by the names in ``BENCHMARK.json``.
"""
