"""chipbench: the on-chip benchmark of paddle_tpu (BENCHMARK.json).

The yardstick lives here and only here: traffic generation, the
reduction from traces, spans and counters to metrics, the table of
peaks, the operation and byte counts, each configuration's plain
reference and the comparison that decides ``correct``. From the program
it takes the system under test and its spans, counters and kernel
names. ``python3 -m chipbench --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell in one process; see README.md.
"""
