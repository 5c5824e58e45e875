"""The motif cell's predicates through the served path, per predicate,
against a float64 brute force.

The 12 PROSITE predicates are read from the benchmark's traffic file at
collection time, so this list is the cell's.  Rows are Swiss-Prot-shaped
protein sequences with seeded Gaussian vectors.  A predicate that the
draw satisfies in fewer than k rows gets literal instances planted, so
every answer is a full top-k.  All 12 requests go through
``ContinuousBatcher`` as one wave, on a fresh index and again after
churn: inserts that carry each motif (the delta tail) and deletes of
current answers (tombstones), under chain covers and residual LIKE.
"""

import json
import os

import numpy as np
import pytest

from repro.core.predicate import And, Contains, Like, Not, Or, \
    parse_predicate
from repro.core.vectormaton import VectorMatonConfig
from repro.data.corpora import swissprot_sequences
from repro.serve.batching import ContinuousBatcher
from repro.serve.engine import Request, RetrievalEngine

TRAFFIC = os.path.join(os.path.dirname(__file__), "..", "bench", "traffic",
                       "swissprot.prosite.json")
with open(TRAFFIC) as _f:
    PREDICATES = json.load(_f)["predicates"]

N, DIM, K, SEED = 1024, 64, 10, 7


def _pieces(node):
    """(anchor, text) pieces whose splice into a sequence satisfies
    ``node``: a CONTAINS literal anywhere, a LIKE pattern with ``_`` as
    ``A`` and ``%`` as nothing (anchored where the pattern has no
    leading or trailing ``%``), an OR's first branch, every AND branch
    that is not a NOT."""
    if isinstance(node, Contains):
        return [("mid", node.pattern)]
    if isinstance(node, Like):
        toks = node.tokens()
        text = "".join(c if kind == "lit" else "A" if kind == "one" else ""
                       for kind, c in toks)
        anchor = ("start" if toks[0][0] != "any" else
                  "end" if toks[-1][0] != "any" else "mid")
        return [(anchor, text)]
    if isinstance(node, Or):
        return _pieces(node.children[0])
    if isinstance(node, And):
        return [p for c in node.children if not isinstance(c, Not)
                for p in _pieces(c)]
    return []


def _plant(seq: str, pieces) -> str:
    mids = [t for a, t in pieces if a == "mid"]
    for i, text in enumerate(mids):
        at = (i + 1) * len(seq) // (len(mids) + 1)
        seq = seq[:at] + text + seq[at + len(text):]
    for anchor, text in pieces:
        if anchor == "start":
            seq = text + seq[len(text):]
        elif anchor == "end":
            seq = seq[:len(seq) - len(text)] + text
    return seq


def _rows():
    """Swiss-Prot-shaped rows, each predicate planted until at least K
    rows satisfy it."""
    seqs = swissprot_sequences(N, SEED)
    preds = [parse_predicate(p) for p in PREDICATES]
    hosts = iter([i for i in np.random.default_rng(SEED).permutation(N)
                  if len(seqs[i]) >= 60])
    for _ in range(3):
        short = [p for p in preds
                 if sum(p.matches(s) for s in seqs) < K]
        if not short:
            break
        for p in short:
            need = K - sum(p.matches(s) for s in seqs)
            while need > 0:
                i = next(hosts)
                planted = _plant(seqs[i], _pieces(p))
                if p.matches(planted):
                    seqs[i] = planted
                    need -= 1
    assert all(sum(p.matches(s) for s in seqs) >= K for p in preds)
    vecs = np.random.default_rng(SEED).standard_normal(
        (N, DIM)).astype(np.float32)
    return vecs, seqs


def _serve_wave(engine, queries):
    """The 12 requests through the batcher as one wave."""
    batcher = ContinuousBatcher(engine, max_wave=len(PREDICATES))
    waves = []
    batcher.on_wave_start = waves.append
    tickets = [batcher.submit(Request(vector=q, pattern=p, k=K))
               for q, p in zip(queries, PREDICATES)]
    out = batcher.drain()
    batcher.close()
    assert waves == [0]
    return [out[t] for t in tickets]


def _brute_force(vecs, seqs, live, q, pattern):
    pred = parse_predicate(pattern)
    ids = np.array([i for i in range(len(seqs))
                    if live[i] and pred.matches(seqs[i])], np.int64)
    diff = vecs[ids].astype(np.float64) - q.astype(np.float64)
    dist = np.einsum("nd,nd->n", diff, diff)
    order = np.lexsort((ids, dist))[:K]
    return ids[order], dist[order]


def _check(resp, want):
    ids, dist = want
    assert len(ids) == K
    assert resp.ids.tolist() == ids.tolist()
    np.testing.assert_allclose(resp.distances, dist, rtol=1e-5, atol=1e-4)


@pytest.fixture(scope="module")
def fresh():
    vecs, seqs = _rows()
    engine = RetrievalEngine(vecs, seqs,
                             VectorMatonConfig(T=10 ** 9, backend="jax"))
    queries = np.random.default_rng(SEED + 1).standard_normal(
        (len(PREDICATES), DIM)).astype(np.float32)
    live = np.ones(N, bool)
    resps = _serve_wave(engine, queries)
    want = [_brute_force(vecs, seqs, live, q, p)
            for q, p in zip(queries, PREDICATES)]
    return engine, vecs, seqs, queries, resps, want


@pytest.fixture(scope="module")
def churned(fresh):
    """Two inserts per predicate that carry its motif next to its query,
    then deletes of each answer's nearest row and of every fifth
    insert."""
    engine, vecs, seqs, queries, resps, _ = fresh
    rng = np.random.default_rng(SEED + 2)
    vecs, seqs = list(vecs), list(seqs)
    donors = swissprot_sequences(2 * len(PREDICATES), SEED + 3)
    inserted = []
    for r, (q, p) in enumerate(zip(queries, PREDICATES)):
        pred = parse_predicate(p)
        for j in range(2):
            seq = _plant(donors[2 * r + j].ljust(60, "G"),
                         _pieces(pred))
            assert pred.matches(seq)
            v = q + 0.3 * rng.standard_normal(DIM).astype(np.float32)
            nid = engine.insert(v, seq)
            assert nid == len(seqs)
            vecs.append(v)
            seqs.append(seq)
            inserted.append(nid)
    assert engine.maintenance_stats()["delta_pending"] == len(inserted)
    live = np.ones(len(seqs), bool)
    victims = {int(resp.ids[0]) for resp in resps} | set(inserted[::5])
    for i in sorted(victims):
        engine.delete(i)
        live[i] = False
    vecs = np.stack(vecs)
    resps = _serve_wave(engine, queries)
    want = [_brute_force(vecs, seqs, live, q, p)
            for q, p in zip(queries, PREDICATES)]
    return resps, want


@pytest.mark.parametrize("r", range(len(PREDICATES)), ids=PREDICATES)
def test_motif_fresh_index(fresh, r):
    _check(fresh[4][r], fresh[5][r])


@pytest.mark.parametrize("r", range(len(PREDICATES)), ids=PREDICATES)
def test_motif_after_churn(churned, r):
    _check(churned[0][r], churned[1][r])
