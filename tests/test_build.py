"""The index build against a plain per-state reference, and the served
path on protein rows against a float64 brute force.

The reference builds every state's base set as the paper states it, one
``np.setdiff1d`` of V_u and V_inherit(u) per state, with inherit(u) the
first direct successor of largest V; the build's array passes must give
the same ``kind``, ``base_ptr``, ``base_ids`` and ``inherit``, to the
id.  The served answers are those of ``RetrievalEngine(backend="jax")``
for the Swiss-Prot motif mix, against the rows that the benchmark's own
predicate evaluator (``bench/predicates.py``) says satisfy each one.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.packed import KIND_GRAPH, KIND_RAW, ranges
from repro.core.vectormaton import VectorMaton, VectorMatonConfig
from repro.data.corpora import make_corpus, swissprot_sequences
from repro.serve.engine import RetrievalEngine

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import predicates  # noqa: E402

# the strategy each predicate of the mix compiles to, where rows have it
MECHANISM = ["chain"] * 6 + ["residual"] * 4 + ["scan", "residual"]
MIX = json.loads((ROOT / "bench" / "traffic" / "swissprot.prosite.json")
                 .read_text())["predicates"]


def _corpus(name):
    if name == "swissprot":
        seqs = swissprot_sequences(600, 11)
        return np.random.default_rng(11).standard_normal(
            (600, 16)).astype(np.float32), seqs
    return make_corpus(name, seed=3, scale=0.2)


def _reference(esam, cfg):
    """(kind, base_ptr, base_ids, inherit), one state at a time."""
    n = esam.num_states
    inherit = np.full(n, -1, np.int64)
    kind = np.empty(n, np.int8)
    ptr = np.zeros(n + 1, np.int64)
    segs = []
    for u in range(n):
        best = 0
        for v in esam.trans[u].values() if cfg.reuse else ():
            if len(esam.state_ids(v)) > best:
                inherit[u], best = v, len(esam.state_ids(v))
        vu = esam.state_ids(u)
        base = (vu if inherit[u] == -1 else np.setdiff1d(
            vu, esam.state_ids(inherit[u]), assume_unique=True))
        graph = len(base) > 0 and (not cfg.skip_build or len(base) >= cfg.T)
        kind[u] = KIND_GRAPH if graph else KIND_RAW
        segs.append(base)
        ptr[u + 1] = ptr[u] + len(base)
    return kind, ptr, np.concatenate(segs), inherit


@pytest.mark.parametrize("name,cfg", [
    ("swissprot", {}),
    ("prot", {}),
    ("words", {"T": 40}),
    ("words", {"reuse": False}),
    ("words", {"skip_build": False, "T": 10 ** 9}),
])
def test_build_equals_the_per_state_reference(name, cfg):
    """The index's state-ordered CSR equals the reference to the id, and
    the runtime holds each state's segment as it is, wherever
    ``chain_layout`` put it."""
    vecs, seqs = _corpus(name)
    if not cfg.get("skip_build", True):
        seqs, vecs = seqs[:60], vecs[:60]         # a graph per state
    config = VectorMatonConfig(**{"T": 10 ** 9, "M": 8, "ef_con": 32, **cfg})
    vm = VectorMaton(vecs, seqs, config)
    kind, ptr, ids, inherit = _reference(vm.esam, config)
    got_kind, got_ptr, got_ids = vm.state_index.flatten()
    assert np.array_equal(vm.inherit, inherit)
    assert np.array_equal(got_kind, kind)
    assert np.array_equal(got_ptr, ptr)
    assert np.array_equal(got_ids, ids)
    rt = vm.runtime
    assert np.array_equal(rt.inherit, inherit)
    assert np.array_equal(rt.kind, kind)
    assert np.array_equal(rt.base_hi - rt.base_lo, np.diff(ptr))
    assert np.array_equal(rt.base_ids[ranges(rt.base_lo, np.diff(ptr))], ids)
    if (kind == KIND_GRAPH).any():
        assert set(rt.graph_objs) == set(np.nonzero(kind == KIND_GRAPH)[0])


@pytest.mark.parametrize("name", ["swissprot", "prot", "words"])
def test_chain_covers_come_in_few_runs(name):
    """A state's chain cover ships at most log2(states) + 1 raw runs
    (``chain_layout``'s heavy paths), where the chain itself walks
    hundreds of states, and the runs hold V_state exactly."""
    vecs, seqs = _corpus(name)
    vm = VectorMaton(vecs, seqs, VectorMatonConfig(T=10 ** 9))
    rt, esam = vm.runtime, vm.esam
    bound = int(np.log2(esam.num_states)) + 1
    longest = 0
    some = np.random.default_rng(0).permutation(np.arange(1, esam.num_states))
    for u in some[:1000].tolist():
        cov = rt.chain_cover(u)
        walked, v = 0, u
        while v != -1:
            walked, v = walked + 1, int(rt.inherit[v])
        longest = max(longest, walked)
        assert len(cov.raw_segments) <= bound, u
        got = np.concatenate([rt.base_ids[lo:hi]
                              for lo, hi in cov.raw_segments])
        assert np.array_equal(np.sort(got), esam.state_ids(u)), u
    if name == "swissprot":
        assert longest >= 100


@pytest.fixture(scope="module")
def served():
    """Every predicate of the mix, three requests each, through the
    device executor's served path, and the rows that satisfy each."""
    vecs, seqs = _corpus("swissprot")
    eng = RetrievalEngine(vecs, seqs, VectorMatonConfig(
        T=10 ** 9, backend="jax"))
    rng = np.random.default_rng(5)
    pats = [p for p in MIX for _ in range(3)]
    q = rng.standard_normal((len(pats), vecs.shape[1])).astype(np.float32)
    got = eng.query_batch(q, pats, 10)
    return vecs, q, pats, got, predicates.members(MIX, seqs), eng


@pytest.mark.parametrize("i", range(len(MIX)))
def test_served_motif_answers_equal_the_brute_force(served, i):
    vecs, q, pats, got, members, *_ = served
    for r in np.nonzero(np.asarray(pats) == MIX[i])[0]:
        ids = members[MIX[i]]
        d = ((vecs[ids].astype(np.float64) - q[r]) ** 2).sum(1)
        want = ids[np.lexsort((ids, d))[:10]]
        dist, answer = got[r][0], got[r][1]
        assert np.array_equal(np.asarray(answer), want), MIX[i]
        np.testing.assert_allclose(dist, np.sort(d)[:10], rtol=1e-5,
                                   atol=1e-4)


def test_each_motif_compiles_to_its_mechanism(served):
    *_, members, eng = served
    for text, mechanism in zip(MIX, MECHANISM):
        sources = eng.index.compile(text).sources
        if len(members[text]) == 0 and mechanism != "residual":
            assert not sources, text            # a miss: no device work
            continue
        assert {s.strategy for s in sources} == {mechanism}, text


def _mixes_lower_nothing_after_bursts(quantize):
    from repro.serve import telemetry
    mix = [p.replace("GKT AND DEAD", "GKT AND DE") for p in MIX]
    vecs, seqs = _corpus("swissprot")
    eng = RetrievalEngine(vecs, seqs, VectorMatonConfig(
        T=10 ** 9, backend="jax", quantize=quantize))
    q = np.random.default_rng(7).standard_normal(
        (3 * len(MIX), vecs.shape[1])).astype(np.float32)
    for p in mix:
        for n in range(1, 5):
            eng.query_batch(q[:n], [p] * n, 10)
    before = telemetry.compile_stats()["jit_compiles"]
    order = np.random.default_rng(8)
    for reps in (1, 2, 3):
        pats = [p for p in mix for _ in range(reps)]
        order.shuffle(pats)
        eng.query_batch(q[:len(pats)], pats, 10)
    assert telemetry.compile_stats()["jit_compiles"] == before
    return eng


def test_warming_each_motif_alone_warms_every_mix():
    """Bursts of 1 to 4 requests of one predicate at a time (what the
    benchmark's warm-up sends first) lower every program that waves
    mixing the motif set then need: chain covers, masked scans and
    residual rankings side by side move the scan's candidate region
    alone, and the merge and each ranking keep their shapes.  At 600
    rows ``GKT AND DE`` stands in for ``GKT AND DEAD``: a masked scan of
    a few dozen rows, as the latter is at 32,768."""
    _mixes_lower_nothing_after_bursts("none")


def test_sq8_warming_leaves_escalations_nothing_to_lower():
    """The same under the SQ8 default: a floored shape runs its fp32
    program with its first quantized batch, so the escalations and
    per-shape flips that follow (dense covers such as ``LL`` fail the
    certificate) lower nothing, whichever batches certified."""
    rt = _mixes_lower_nothing_after_bursts("sq8").index.runtime
    assert rt.chains
    assert rt.sq8_stats["batches"] > 0
    assert rt._sq8_fp32_warm and None not in rt._sq8_bad_streak


def test_a_runtime_with_chains_floors_every_wave():
    """Rows with sequences of their own give chains, and every launch of
    such a runtime takes the floors, one segment per query row or not;
    one label per row gives none."""
    from repro.kernels import ops
    assert ops.descriptor_extents(1, np.asarray([83_333]), 0, 0,
                                  chains=True) == (128, 2 ** 17, 1024, 0, 64)
    labels = [chr(ord("a") + i % 12) for i in range(240)]
    vecs = np.random.default_rng(2).standard_normal((240, 8)).astype(
        np.float32)
    assert not VectorMaton(vecs, labels,
                           VectorMatonConfig(T=10 ** 9)).runtime.chains
    assert VectorMaton(*_corpus("swissprot"),
                       VectorMatonConfig(T=10 ** 9)).runtime.chains


@pytest.mark.parametrize("q, lens, n_res, want", [
    (1, [83_333], 0, (128, 2 ** 17, 0, 0, 8)),           # one label
    (2, [83_333, 98_626], 0, (128, 2 ** 18, 0, 0, 8)),   # two labels
    (1, [28_000, 800], 0, (128, 2 ** 15, 1024, 0, 64)),  # a two-run chain
    (2, [], 30, (128, 0, 1024, 0, 0)),                   # a masked scan
    (2, [2_300, 9], 30, (128, 4096, 1024, 0, 64)),       # mixed
])
def test_floors_only_where_a_wave_mixes(q, lens, n_res, want):
    """A wave of one segment per query row and no tail keeps the plain
    power-of-two buckets; chain covers of several runs or a resident
    tail put the wave on the floors."""
    from repro.kernels import ops
    assert ops.descriptor_extents(q, np.asarray(lens), n_res, 0) == want
