"""Retrieval engine integration: index -> serve -> maintain."""

import numpy as np
import pytest

from repro.core.baselines import ground_truth, recall
from repro.core.predicate import parse_predicate
from repro.core.vectormaton import VectorMatonConfig
from repro.data.corpora import SPECS, make_corpus, sample_patterns
from repro.serve.engine import Request, RetrievalEngine


@pytest.fixture(scope="module")
def engine():
    vecs, seqs = make_corpus("words", scale=0.2)
    return RetrievalEngine(vecs, seqs,
                           VectorMatonConfig(T=30, M=8, ef_con=50)), seqs


def test_serve_batch_recall(engine):
    eng, seqs = engine
    pats = sample_patterns(seqs, 2, 40)
    rng = np.random.default_rng(0)
    dim = eng.index.vectors.shape[1]
    reqs = [Request(vector=rng.standard_normal(dim).astype(np.float32),
                    pattern=p, k=10) for p in pats]
    resps = eng.serve_batch(reqs)
    recs = [recall(r.ids, ground_truth(eng.index.vectors, eng.index.esam,
                                       req.pattern, req.vector, req.k))
            for req, r in zip(reqs, resps)]
    assert np.mean(recs) >= 0.95
    assert all(r.latency_s < 2.0 for r in resps)


def test_serve_batch_equals_per_request(engine):
    """Regression for the dead `by_state` grouping: coalesced batched
    execution must return identical (distance, id) results to serving the
    same requests one at a time — including repeated patterns and misses."""
    eng, seqs = engine
    rng = np.random.default_rng(9)
    dim = eng.index.vectors.shape[1]
    pats = sample_patterns(seqs, 2, 10) + ["@@nope@@"]
    pats = [pats[i % len(pats)] for i in range(30)]   # force coalescing
    reqs = [Request(vector=rng.standard_normal(dim).astype(np.float32),
                    pattern=p, k=8) for p in pats]
    plan = eng.index.plan([r.pattern for r in reqs])
    assert plan.coalesced >= 4    # same-state requests actually share entries
    batched = eng.serve_batch(reqs)
    for req, resp in zip(reqs, batched):
        single = eng.serve(req)
        assert np.array_equal(single.ids, resp.ids)
        np.testing.assert_allclose(single.distances, resp.distances,
                                   rtol=1e-6)


def test_serve_batch_mixed_k(engine):
    eng, seqs = engine
    rng = np.random.default_rng(10)
    dim = eng.index.vectors.shape[1]
    pats = sample_patterns(seqs, 2, 4)
    reqs = [Request(vector=rng.standard_normal(dim).astype(np.float32),
                    pattern=p, k=3 + (i % 2) * 5)
            for i, p in enumerate(pats)]
    for req, resp in zip(reqs, eng.serve_batch(reqs)):
        assert len(resp.ids) <= req.k
        single = eng.serve(req)
        assert np.array_equal(single.ids, resp.ids)


def test_corpora_shapes():
    for name, spec in SPECS.items():
        vecs, seqs = make_corpus(name, scale=0.05)
        assert vecs.shape[1] == spec.dim
        assert len(vecs) == len(seqs)
        assert all(len(s) > 0 for s in seqs)
        assert set("".join(seqs[:10])) <= set(spec.alphabet)


def test_engine_checkpoint_restore(engine, tmp_path):
    eng, seqs = engine
    path = str(tmp_path / "engine_ckpt")
    eng.checkpoint(path)
    eng2 = RetrievalEngine.restore(path)
    rng = np.random.default_rng(1)
    dim = eng.index.vectors.shape[1]
    q = rng.standard_normal(dim).astype(np.float32)
    p = sample_patterns(seqs, 2, 1)[0]
    d1, i1 = eng.index.query(q, p, 5)
    d2, i2 = eng2.index.query(q, p, 5)
    assert np.array_equal(i1, i2)


def test_engine_insert_then_query(engine):
    eng, seqs = engine
    rng = np.random.default_rng(2)
    dim = eng.index.vectors.shape[1]
    v = rng.standard_normal(dim).astype(np.float32)
    nid = eng.insert(v, "zqzqzq")
    r = eng.serve(Request(vector=v, pattern="zqzq", k=3))
    assert nid in r.ids.tolist()
    eng.delete(nid)
    r = eng.serve(Request(vector=v, pattern="zqzq", k=3))
    assert nid not in r.ids.tolist()



SMOKE_FORMS = ["ab", "cd", "a", "ab AND cd", "ab OR cd", "NOT ab",
               "LIKE '%a%b%'"]


@pytest.fixture(scope="module")
def smoke_wave():
    """One batch of plain patterns (``ab`` four times, so the plan
    coalesces) and boolean predicates over a graph-backed index."""
    rng = np.random.default_rng(0)
    seqs = ["".join(rng.choice(list("abcd"), size=rng.integers(5, 14)))
            for _ in range(120)]
    vecs = rng.standard_normal((120, 16)).astype(np.float32)
    eng = RetrievalEngine(vecs, seqs, VectorMatonConfig(T=20, M=8,
                                                        ef_con=40))
    pats = ["ab", "ab", "ab"] + SMOKE_FORMS
    reqs = [Request(vector=rng.standard_normal(16).astype(np.float32),
                    pattern=p, k=5) for p in pats]
    return eng, seqs, reqs, eng.serve_batch(reqs)


@pytest.mark.parametrize("form", SMOKE_FORMS)
def test_batched_engine_smoke(smoke_wave, form):
    eng, seqs, reqs, resps = smoke_wave
    pred = parse_predicate(form)
    cases = [(q, r) for q, r in zip(reqs, resps) if q.pattern == form]
    assert cases
    for req, resp in cases:
        assert np.array_equal(eng.serve(req).ids, resp.ids)
        assert all(pred.matches(seqs[i]) for i in resp.ids.tolist())
