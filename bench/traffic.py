"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``
and makes the cell's open-loop schedule from the seed.

Every seed gets the same work in another order: ``rate_per_s * seconds``
requests, in runs of ``len(predicates)`` that each hold every predicate
of the mix once (so the costly ones cannot bunch more under one seed
than another), and the gaps between sends a fixed set (the quantiles of
the exponential distribution at the mix's rate, so the sends are
Poisson-like) shuffled.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import corpus

TRAFFIC = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    mix = json.loads((TRAFFIC / f"{name}.json").read_text())
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"traffic {name}: unknown arrivals "
                         f"{mix.get('arrivals')!r}")
    return mix


def schedule(mix: dict, cfg: dict, seed: int, seconds: float,
             stream: int = 0, rate: float | None = None) -> list:
    """``[(offset_s, query vector, predicate text)]`` in send order."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(np.random.SeedSequence([seed, stream, 0x7A]))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= seconds / gaps.sum()
    gaps = gaps[rng.permutation(n)]
    offsets = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    preds = mix["predicates"]
    # each run of len(preds) requests holds every predicate once; the
    # last, short run the first predicates of the mix
    full, rest = divmod(n, len(preds))
    pats = np.concatenate([rng.permutation(len(preds)) for _ in range(full)]
                          + [rng.permutation(rest)]).astype(np.int64)
    vecs = corpus.source(cfg).queries(n, seed, cfg, stream)
    return [(float(offsets[i]), vecs[i], preds[pats[i]]) for i in range(n)]
