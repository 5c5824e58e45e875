"""The data of the label configurations is pinned: at the rehearsal size,
the rows' vectors and sequences, 64 requests of the cell's schedule (send
offsets, query vectors, predicates), the warm-up's query vectors and
each predicate's member ids hash to what the harness made before
configurations could name a corpus of their own.  Any change to them
moves every earlier reading of these cells."""

from __future__ import annotations

import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import corpus, predicates, traffic  # noqa: E402
from bench import run as bench_run  # noqa: E402

SEED, COUNT = 2147483659, 64

# the two label configurations share their label draws, so their
# sequences and member ids agree
MEMBERS = {
    "a": "a3be20d18bac2bfe", "b": "fbe8b744291824ff",
    "c": "1126b7753b9655cc", "d": "05fee6221b52da69",
    "e": "14586ee23927db68", "f": "0c6374b3aa80016d",
    "g": "1d06cdf9f805086b", "h": "b52f8f8bb81ccedd",
    "i": "137e032d2ab5a06b", "j": "0ed5beba19059e6f",
    "k": "1df2645a240f7601", "l": "ddd54a35a7b32db8"}
PINS = {
    "sift1m-tags.contains": {
        "vectors": "27e9966a44b7fde0", "sequences": "3a5d96897d142437",
        "offsets": "e7b28255711737ad", "queries": "74786f97cf301d58",
        "warm_queries": "9415e1fe6edbd544", "patterns": "d90bac955f268ddb",
        "members": MEMBERS},
    "glove100-tags.contains": {
        "vectors": "c4b7b0fbcde40063", "sequences": "3a5d96897d142437",
        "offsets": "3be4bfcd381a6af8", "queries": "7df2f99639170ffb",
        "warm_queries": "a07a9c41d81c61d9", "patterns": "d90bac955f268ddb",
        "members": MEMBERS},
}


def _h(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()[:16]


@pytest.mark.parametrize("cell", sorted(PINS))
def test_label_cells_make_the_pinned_data(cell):
    _, _, cfg, mix = bench_run.load_cell(cell)
    assert "corpus" not in cfg
    vecs, seqs = corpus.rows(cfg, bench_run.REHEARSE_ROWS, SEED)
    sched = traffic.schedule(mix, cfg, SEED, COUNT / mix["rate_per_s"])
    assert len(sched) == COUNT
    warm = corpus.source(cfg).queries(COUNT, SEED, cfg, stream=1)
    got = {"vectors": _h(vecs.tobytes()),
           "sequences": _h("\n".join(seqs).encode()),
           "offsets": _h(np.asarray([o for o, _, _ in sched],
                                    np.float64).tobytes()),
           "queries": _h(np.asarray([v for _, v, _ in sched],
                                    np.float32).tobytes()),
           "warm_queries": _h(warm.tobytes()),
           "patterns": _h("\n".join(p for _, _, p in sched).encode()),
           "members": {p: _h(ids.tobytes()) for p, ids in
                       predicates.members(mix["predicates"], seqs).items()}}
    assert got == PINS[cell]
