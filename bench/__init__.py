"""The chip benchmark of the GP fit -> serve system (see ``BENCHMARK.json``).

One command runs one cell: ``python3 bench/run.py --workload <cell> --seed
<n> --seconds <s> --trace <0|1>``. Everything that belongs to one
configuration, traffic kind or per-layer metric sits in a file of its own
(``configs/``, ``workloads/``, ``traffic/``, ``metrics/``), found by name.
"""
