"""Chip benchmark of the served filtered k-NN path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Configurations,
traffic mixes, corpora, metric readers and kernel work functions are
files of their own under this directory, found by name
(``load_module``).
"""

import functools
import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


@functools.cache
def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module, loaded once per process."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
