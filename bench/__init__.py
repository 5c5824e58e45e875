"""Chip benchmark of the served filtered k-NN path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Configurations,
traffic mixes, metric readers and kernel work functions are files of
their own under this directory, found by name.
"""
