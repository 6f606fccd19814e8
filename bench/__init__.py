"""On-chip benchmark of the PDHG LP solver (see ``BENCHMARK.json``).

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on the chip and prints one JSON line.
Everything that decides a number lives here: instance generation,
the known-optimum comparison, the trace reduction and the peak table.
"""
