"""End-to-end system behaviour: the paper's full pipeline on one box.

Vectors are given: seeded Gaussian vectors beside a corpus's sequences
-> build VectorMaton -> serve pattern-constrained queries ->
checkpoint/restore -> keep serving.
"""

import os

import numpy as np

from repro.core.baselines import ground_truth, recall
from repro.core.vectormaton import VectorMatonConfig
from repro.data.corpora import make_corpus, sample_patterns
from repro.serve.engine import Request, RetrievalEngine


def test_end_to_end_pipeline(tmp_path):
    # 1. a corpus's sequences, each with a seeded Gaussian vector
    _, seqs = make_corpus("words", scale=0.1)
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((len(seqs), 32)).astype(np.float32)

    # 2. index + serve
    eng = RetrievalEngine(vecs, seqs,
                          VectorMatonConfig(T=20, M=8, ef_con=40))
    pats = sample_patterns(seqs, 2, 20)
    recs = []
    for p in pats:
        q = vecs[rng.integers(0, len(vecs))]
        r = eng.serve(Request(vector=q, pattern=p, k=5))
        gt = ground_truth(eng.index.vectors, eng.index.esam, p, q, 5)
        recs.append(recall(r.ids, gt))
    assert np.mean(recs) >= 0.95

    # 3. checkpoint / restore / serve again
    ck = os.path.join(tmp_path, "sys_ckpt")
    eng.checkpoint(ck)
    eng2 = RetrievalEngine.restore(ck)
    q = vecs[0]
    r1 = eng.serve(Request(vector=q, pattern=pats[0], k=5))
    r2 = eng2.serve(Request(vector=q, pattern=pats[0], k=5))
    assert np.array_equal(r1.ids, r2.ids)
