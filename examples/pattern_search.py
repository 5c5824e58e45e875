"""Serve VectorMaton with batched pattern-constrained requests — the
end-to-end serving driver.

Vectors are given, as in the paper: each record of a corpus gets a seeded
Gaussian vector beside its sequence.  The example indexes the records,
serves a batch of mixed-pattern requests (plain CONTAINS plus boolean
AND/OR/NOT and LIKE predicates), reports QPS and recall, serves hybrid
attribute predicates, then checkpoints and restores the engine and
serves again.

    PYTHONPATH=src python examples/pattern_search.py
"""

import os
import tempfile
import time

import numpy as np

from repro.core.baselines import ground_truth, recall
from repro.core.predicate import parse_predicate, quote_literal
from repro.core.vectormaton import VectorMatonConfig
from repro.data.corpora import make_corpus, sample_patterns
from repro.serve.engine import Request, RetrievalEngine

# --- 1. a corpus of (sequence) records ----------------------------------
_, sequences = make_corpus("mtg", scale=0.05)
print(f"corpus: {len(sequences)} records, "
      f"total length {sum(len(s) for s in sequences)}")

# --- 2. the given vectors: seeded Gaussian, one per record --------------
vectors = np.random.default_rng(0).standard_normal(
    (len(sequences), 64)).astype(np.float32)
print(f"{len(vectors)} vectors (dim={vectors.shape[1]})")

# --- 3. index + serve batched requests ----------------------------------
engine = RetrievalEngine(vectors, sequences,
                         VectorMatonConfig(T=40, M=8, ef_con=50))
print("index:", engine.index.stats())

rng = np.random.default_rng(1)
patterns = (sample_patterns(sequences, 2, 40, seed=11)
            + sample_patterns(sequences, 3, 40, seed=11)
            + sample_patterns(sequences, 4, 40, seed=11))
requests = [Request(vector=vectors[rng.integers(len(vectors))]
                    + 0.1 * rng.standard_normal(vectors.shape[1]
                                                ).astype(np.float32),
                    pattern=p, k=10) for p in patterns]
t0 = time.time()
responses = engine.serve_batch(requests)
dt = time.time() - t0
recalls = [recall(resp.ids,
                  ground_truth(engine.index.vectors, engine.index.esam,
                               req.pattern, req.vector, req.k))
           for req, resp in zip(requests, responses)]
print(f"{len(requests)} requests in {dt:.2f}s ({len(requests)/dt:.0f} QPS)"
      f", mean recall@10 = {np.mean(recalls):.3f}")

# --- 4. boolean predicates: AND / OR / NOT / LIKE -----------------------
p2 = sample_patterns(sequences, 2, 8, seed=23)
p3 = sample_patterns(sequences, 3, 8, seed=23)
long_seqs = [s for s in sequences if len(s) >= 8]


def _esc(text: str) -> str:
    """Backslash-escape LIKE wildcards so sampled substrings match
    literally even when they contain ``%`` or ``_``."""
    return (text.replace("\\", "\\\\").replace("%", r"\%")
            .replace("_", r"\_"))



# quote_literal handles every grammar hazard in a sampled substring —
# spaces, parens, comparison chars, embedded quotes (doubled: 'it''s')
predicates = (
    [f"{quote_literal(a)} AND {quote_literal(b)}"
     for a, b in zip(p2[:3], p3[:3])]
    + [f"{quote_literal(a)} OR {quote_literal(b)}"
       for a, b in zip(p3[:3], p3[3:6])]
    + [f"{quote_literal(a)} AND NOT {quote_literal(b)}"
       for a, b in zip(p2[3:5], p3[5:7])]
    + [f"LIKE {quote_literal('%' + _esc(s[:3]) + '%' + _esc(s[-3:]) + '%')}"
       for s in long_seqs[:3]]                                # ordered LIKE
)
pred_reqs = [Request(vector=vectors[rng.integers(len(vectors))]
                     + 0.1 * rng.standard_normal(vectors.shape[1]
                                                 ).astype(np.float32),
                     pattern=p, k=10) for p in predicates]
plan = engine.index.plan(predicates)
print(f"predicate plan: {len(plan.entries)} entries, "
      f"strategies={dict(plan.strategies)}")
t0 = time.time()
pred_resps = engine.serve_batch(pred_reqs)
dt = time.time() - t0
for req, resp in zip(pred_reqs, pred_resps):
    pred = parse_predicate(req.pattern)
    assert all(pred.matches(sequences[i]) for i in resp.ids.tolist()), \
        req.pattern
print(f"{len(pred_reqs)} boolean-predicate requests in {dt:.2f}s "
      f"({len(pred_reqs)/dt:.0f} QPS), all results satisfy their "
      f"predicates")

# --- 5. hybrid structured predicates: tags + ranges + patterns ----------
genres = ["rock", "jazz", "pop"]
attributes = [{"genre": genres[int(rng.integers(0, 3))],
               "price": float(np.round(rng.uniform(0, 20), 2))}
              for _ in sequences]
attr_engine = RetrievalEngine(
    vectors, sequences,
    VectorMatonConfig(T=40, M=8, ef_con=50,
                      schema={"genre": "tag", "price": "numeric"}),
    attributes=attributes)
hybrid = ([f"genre = {quote_literal(g)}" for g in genres]
          + ["price < 5", "price >= 3 AND price <= 12"]
          + [f"{quote_literal(p)} AND genre = 'jazz'" for p in p2[:2]]
          + [f"{quote_literal(p)} AND price < 10" for p in p3[:2]])
hyb_reqs = [Request(vector=vectors[rng.integers(len(vectors))]
                    + 0.1 * rng.standard_normal(vectors.shape[1]
                                                ).astype(np.float32),
                    pattern=p, k=10) for p in hybrid]
t0 = time.time()
hyb_resps = attr_engine.serve_batch(hyb_reqs)
dt = time.time() - t0
for req, resp in zip(hyb_reqs, hyb_resps):
    pred = parse_predicate(req.pattern)
    assert all(pred.matches(sequences[i], attributes[i])
               for i in resp.ids.tolist()), req.pattern
print(f"{len(hyb_reqs)} hybrid attribute+pattern requests in {dt:.2f}s "
      f"({len(hyb_reqs)/dt:.0f} QPS), all results satisfy their "
      f"predicates")

# --- 6. fault tolerance: checkpoint, restore, keep serving --------------
with tempfile.TemporaryDirectory() as tmp:
    path = os.path.join(tmp, "vectormaton_engine")
    engine.checkpoint(path)
    restored = RetrievalEngine.restore(path)
r1 = engine.serve(requests[0])
r2 = restored.serve(requests[0])
assert np.array_equal(r1.ids, r2.ids)
print("checkpoint/restore verified: identical results after restart")
