"""Packed runtime + planner/executor: batched path == per-request path,
maintenance (delete propagation, raw->HNSW promotion) against the runtime."""

import numpy as np
import pytest

from repro.core.packed import KIND_GRAPH, KIND_RAW, PackedRuntime
from repro.core.vectormaton import (VectorMaton, VectorMatonConfig, _HNSW,
                                    _RAW)


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(11)
    n = 220
    seqs = ["".join(rng.choice(list("abcd"),
                               size=rng.integers(5, 16))) for _ in range(n)]
    vecs = rng.standard_normal((n, 20)).astype(np.float32)
    return vecs, seqs


def _build(dataset, **kw):
    vecs, seqs = dataset
    return VectorMaton(vecs, seqs, VectorMatonConfig(M=8, ef_con=50, **kw))


# --------------------------------------------------------------------- #
# packed structure invariants
# --------------------------------------------------------------------- #

def test_chain_csr_cover_is_exact(dataset):
    """The CSR chain cover reproduces V_state disjointly (Lemma 4) for every
    state — the invariant the whole executor rests on."""
    vm = _build(dataset, T=25)
    rt = vm.runtime
    for u in range(vm.esam.num_states):
        cov = rt.chain_ids(u)
        assert len(cov) == len(np.unique(cov))
        assert set(cov.tolist()) == set(vm.esam.state_ids(u).tolist())


def test_packed_kinds_match_state_indexes(dataset):
    vm = _build(dataset, T=25)
    rt = vm.runtime
    for u, idx in enumerate(vm.state_index):
        if idx is None:
            continue
        want = KIND_RAW if idx.kind == _RAW else KIND_GRAPH
        assert rt.kind[u] == want
        seg = rt.base_ids[rt.base_lo[u]:rt.base_hi[u]]
        src = idx.raw_ids if idx.kind == _RAW else np.asarray(idx.graph.ids)
        assert np.array_equal(np.sort(seg), np.sort(np.asarray(src)))


def test_device_arrays_materialized_once(dataset):
    """Acceptance: packed arrays upload once and are reused — the device
    cache object must be identical across queries."""
    vecs, seqs = dataset
    vm = _build(dataset, T=1000)
    vm.config.backend = "jax"
    vm.runtime.backend = "jax"
    rng = np.random.default_rng(0)
    q = rng.standard_normal((2, vecs.shape[1])).astype(np.float32)
    vm.query_batch(q, ["a", "b"], 5)
    dev1 = vm.runtime._dev
    assert dev1 is not None
    vm.query_batch(q, ["ab", "a"], 5)
    assert vm.runtime._dev is dev1
    assert dev1["base_ids"].shape[0] == len(vm.runtime.base_ids)


# --------------------------------------------------------------------- #
# batched executor parity (acceptance criterion)
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("T,label", [(10 ** 6, "raw-only"), (1, "graph-only"),
                                     (25, "mixed")])
def test_batched_equals_per_request(dataset, T, label):
    """For raw-only, graph-only, and mixed chains, query_batch returns
    identical (distance, id) results to the per-request query path."""
    vecs, seqs = dataset
    vm = _build(dataset, T=T)
    rng = np.random.default_rng(3)
    pats = ["a", "ab", "abc", "ba", "dd", "zz", "a", "ab"]  # repeats coalesce
    queries = rng.standard_normal((len(pats),
                                   vecs.shape[1])).astype(np.float32)
    batched = vm.query_batch(queries, pats, 7, ef_search=48)
    for r, p in enumerate(pats):
        d, i = vm.query(queries[r], p, 7, ef_search=48)
        bd, bi = batched[r]
        assert np.array_equal(i, bi), (label, p)
        np.testing.assert_allclose(d, bd, rtol=1e-6)


def test_plan_coalesces_identical_states(dataset):
    vm = _build(dataset, T=25)
    plan = vm.plan(["ab", "ab", "ab", "ab", "ba", "zz"])
    states = [e.state for e in plan.entries]
    assert len(states) == len(set(states)) == 2   # 'zz' misses
    assert plan.misses == [5]
    entry = {e.state: e for e in plan.entries}[vm.esam.walk("ab")]
    assert entry.requests == [0, 1, 2, 3]
    assert plan.coalesced == 3


def test_jax_backend_batched_parity(dataset):
    """Raw-only chains: the segmented Pallas launch must agree with the
    NumPy executor on both backends."""
    vecs, seqs = dataset
    vm_np = _build(dataset, T=10 ** 6)
    vm_jx = _build(dataset, T=10 ** 6)
    vm_jx.config.backend = "jax"
    vm_jx.runtime.backend = "jax"
    rng = np.random.default_rng(4)
    pats = ["a", "ab", "cd", "ab"]
    queries = rng.standard_normal((len(pats),
                                   vecs.shape[1])).astype(np.float32)
    res_np = vm_np.query_batch(queries, pats, 6)
    res_jx = vm_jx.query_batch(queries, pats, 6)
    for (dn, i_n), (dj, ij) in zip(res_np, res_jx):
        assert np.array_equal(i_n, ij)
        np.testing.assert_allclose(dn, dj, atol=2e-4, rtol=1e-4)


# --------------------------------------------------------------------- #
# maintenance against the runtime
# --------------------------------------------------------------------- #

def test_delete_propagates_into_graph_states(dataset):
    """Delete-then-query through a graph state: tombstones must reach the
    per-state HNSW so they are skipped in-scan, not merely filtered after
    crowding out live candidates."""
    vecs, seqs = dataset
    vm = _build(dataset, T=5)          # small T -> graph states on chains
    assert vm.stats()["hnsw_states"] > 0
    pattern = "a"
    st = vm.esam.walk(pattern)
    graph_states = [u for u in vm._chain(st)
                    if vm.state_index[u].kind == _HNSW]
    assert graph_states, "chain has no graph state; pick a denser pattern"
    rng = np.random.default_rng(5)
    q = rng.standard_normal(vecs.shape[1]).astype(np.float32)
    d0, i0 = vm.query(q, pattern, 10, ef_search=64)
    victims = i0[:5].tolist()
    for v in victims:
        vm.delete(v)
    # tombstones landed in the owning graphs
    marked = set()
    for u in graph_states:
        marked |= vm.state_index[u].graph._deleted
    assert set(victims) & marked, "no tombstone reached a chain graph"
    d1, i1 = vm.query(q, pattern, 10, ef_search=64)
    assert not set(victims) & set(i1.tolist())
    # live candidates still fill k (the in-scan skip frees slots)
    ok = set(i for i, s in enumerate(seqs) if pattern in s) - set(victims)
    assert len(i1) == min(10, len(ok))


def test_insert_promotes_raw_to_graph(dataset):
    """Inserting past 4*T must flip a raw state to a graph index against the
    packed runtime (the previously dead promotion branch)."""
    vecs, seqs = dataset
    vm = _build(dataset, T=5)
    dim = vecs.shape[1]
    rng = np.random.default_rng(6)
    assert vm.esam.walk("zz") == -1    # 'z' absent from the base alphabet
    n_ins = 4 * vm.config.T + 2
    ids = [vm.insert(rng.standard_normal(dim).astype(np.float32), "zz")
           for _ in range(n_ins)]
    chain = vm._chain(vm.esam.walk("zz"))
    kinds = [vm.state_index[u].kind for u in chain]
    assert _HNSW in kinds, "no state promoted past 4*T"
    # runtime reflects the promotion and queries stay correct
    assert KIND_GRAPH in [vm.runtime.kind[u] for u in chain]
    q = vm.vectors[ids[0]]
    d, got = vm.query(q, "zz", 5)
    assert set(got.tolist()) <= set(ids)
    assert len(got) == 5


def test_promotion_batched_query_parity(dataset):
    """Insert past 4*T (raw -> HNSW promotion), then verify the BATCHED
    path against per-request queries and the brute-force subset — the
    promotion path previously had no batched-query coverage."""
    vecs, seqs = dataset
    vm = _build(dataset, T=5)
    dim = vecs.shape[1]
    rng = np.random.default_rng(12)
    assert vm.esam.walk("zz") == -1
    n_ins = 4 * vm.config.T + 3
    ids = [vm.insert(rng.standard_normal(dim).astype(np.float32), "zz")
           for _ in range(n_ins)]
    chain = vm._chain(vm.esam.walk("zz"))
    assert _HNSW in [vm.state_index[u].kind for u in chain]
    pats = ["zz", "z", "zz", "a", "zz"]       # promoted state coalesces
    queries = rng.standard_normal((len(pats), dim)).astype(np.float32)
    plan = vm.plan(pats)
    assert plan.coalesced >= 2
    batched = vm.query_batch(queries, pats, 6, ef_search=64)
    for r, p in enumerate(pats):
        d, i = vm.query(queries[r], p, 6, ef_search=64)
        assert np.array_equal(i, batched[r][1]), p
        np.testing.assert_allclose(d, batched[r][0], rtol=1e-6)
    # promoted-state results stay inside the inserted subset
    for r in (0, 2, 4):
        assert set(batched[r][1].tolist()) <= set(ids)
        assert len(batched[r][1]) == 6


def test_insert_lands_in_delta_not_rebuild(dataset):
    """Write path (DESIGN.md §4): an insert must NOT invalidate the packed
    generation — it lands in the delta and is queryable immediately; only
    compact() produces a new runtime, which folds the id into the CSR."""
    vecs, seqs = dataset
    vm = _build(dataset, T=25)
    rt0 = vm.runtime
    builds0 = vm.runtime_builds
    rng = np.random.default_rng(7)
    nid = vm.insert(rng.standard_normal(vecs.shape[1]).astype(np.float32),
                    "abab")
    assert vm.runtime is rt0           # generation survives the insert
    assert vm.runtime_builds == builds0
    st = vm.esam.walk("abab")
    # the id is visible through the delta (chain delta for frozen states,
    # live V set for states created by this insert) and through queries
    if st < rt0.n_states:
        assert nid in rt0.chain_delta_ids(st).tolist()
    d, ids = vm.query(vm.vectors[nid], "abab", 3)
    assert nid in ids.tolist()
    # compaction folds the delta into a fresh generation's CSR
    vm.compact()
    assert vm.runtime is not rt0
    assert vm.runtime.delta.pending == 0
    assert nid in vm.runtime.chain_ids(vm.esam.walk("abab")).tolist()
    d2, ids2 = vm.query(vm.vectors[nid], "abab", 3)
    assert np.array_equal(ids, ids2)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_l2_answers_carry_difference_form_distances(backend):
    """Rows far from the origin, so the scan's expansion
    |x|^2 + |y|^2 - 2x.y cancels most of its float32 digits: the
    answers' distances are sum((x - y)^2) in float32, within two ulps of
    float64."""
    rng = np.random.default_rng(3)
    n, d = 300, 256
    seqs = ["".join(rng.choice(list("ab"), size=6)) for _ in range(n)]
    vecs = (40.0 + rng.standard_normal((n, d))).astype(np.float32)
    q = (40.0 + rng.standard_normal((4, d))).astype(np.float32)
    vm = VectorMaton(vecs, seqs, VectorMatonConfig(T=10 ** 9,
                                                   backend=backend))
    for r, (dist, ids) in enumerate(vm.query_batch(q, ["a"] * 4, 10)):
        exact = ((vecs[ids].astype(np.float64) - q[r]) ** 2).sum(1)
        assert np.all(np.abs(dist - exact) <= 2 * np.spacing(
            exact.astype(np.float32)))
        assert len(ids) == 10 and all("a" in seqs[i] for i in ids)
