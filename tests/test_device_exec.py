"""Device-resident plan execution (DESIGN.md §3): descriptor-driven
segmented scans, bucket-fused multi-graph beams, the shape-bucketed launch
cache, and the device-side merge.

Every device stage has a host/legacy twin kept as its oracle:

  * descriptor scan      vs  materialized candidate upload
  * bucket-fused beams   vs  one launch per graph state
  * device merge         vs  NumPy per-request merge

and the acceptance criteria are asserted directly: zero candidate-id
bytes shipped for frozen-base chain/scan sources, one beam launch per
graph bucket (not per state), and a bounded executable count across a
20-shape batch sweep.
"""

import numpy as np
import pytest

from repro.core.vectormaton import VectorMaton, VectorMatonConfig
from repro.kernels import ops

DIM = 16
K = 6

PREDS = ["a", "ab", "abc", "ba", "a OR cd", "dd"]


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(77)
    n = 230
    seqs = ["".join(rng.choice(list("abcd"),
                               size=rng.integers(5, 15))) for _ in range(n)]
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    return vecs, seqs


def _vm(dataset, **kw):
    vecs, seqs = dataset
    kw.setdefault("backend", "jax")
    kw.setdefault("M", 8)
    kw.setdefault("ef_con", 50)
    return VectorMaton(vecs, seqs, VectorMatonConfig(**kw))


def _queries(n, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (n, DIM)).astype(np.float32)


def _assert_identical(res_a, res_b, tag):
    for r, ((da, ia), (db, ib)) in enumerate(zip(res_a, res_b)):
        assert np.array_equal(ia, ib), (tag, r, ia, ib)
        assert np.array_equal(da, db), (tag, r, da, db)


# --------------------------------------------------------------------- #
# descriptor scans: zero candidate-id upload + parity with materialized
# --------------------------------------------------------------------- #

def test_frozen_chain_ships_zero_candidate_id_bytes(dataset):
    """Acceptance: a warm frozen-base batch of chain/scan sources ships
    NO candidate ids — descriptors resolve against the resident CSR."""
    vm = _vm(dataset, T=10 ** 9)
    q = _queries(len(PREDS), 1)
    res = vm.query_batch(q, PREDS, K)
    rt = vm.runtime
    assert rt.traffic["batches"] == 1
    assert rt.traffic["candidate_id_bytes"] == 0
    assert rt.traffic["row_bytes"] == 0          # nothing past watermark
    assert rt.traffic["descriptor_bytes"] > 0    # planning integers only
    # and the answers are right: numpy backend is the oracle
    vm_np = _vm(dataset, T=10 ** 9, backend="numpy")
    res_np = vm_np.query_batch(q, PREDS, K)
    for (dj, ij), (dn_, in_) in zip(res, res_np):
        assert np.array_equal(ij, in_)
        np.testing.assert_allclose(dj, dn_, atol=2e-4, rtol=1e-4)


def test_masked_scan_ships_ids_but_stays_exact(dataset):
    """Conjunction scans (mask-intersected id sets) still upload their
    surviving ids — only frozen segments are descriptor-eligible — and
    the accounting must say so."""
    vm = _vm(dataset, T=10 ** 9)
    q = _queries(2, 2)
    vm.query_batch(q, ["a AND NOT b", "ab AND cd"], K)
    assert vm.runtime.traffic["candidate_id_bytes"] > 0


def test_descriptor_vs_materialized_parity(dataset):
    """The descriptor-resolved launch must be bit-identical to the legacy
    host-materialized candidate upload (same flat candidate order per
    owner, same kernel)."""
    vm = _vm(dataset, T=10 ** 9)
    preds = PREDS + ["a AND NOT b", "NOT a"]
    q = _queries(len(preds), 3)
    res_desc = vm.query_batch(q, preds, K)
    vm.runtime.use_descriptors = False
    res_mat = vm.query_batch(q, preds, K)
    _assert_identical(res_desc, res_mat, "desc-vs-materialized")


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_descriptor_scan_padded_output_contract(impl):
    """``distance_topk_descriptors`` fed the packed upload returns one
    (padded query row, k) row per bucketed query row with every unfilled
    slot already (+inf, -1): padding rows, a segment smaller than k (one
    of its rows tombstoned) and an owner with no candidates; real rows
    rank exactly their own live candidates."""
    import jax
    rng = np.random.default_rng(31)
    n = 300
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    base_ids = rng.permutation(n).astype(np.int32)
    deleted = np.zeros(n, bool)
    deleted[base_ids[[3, 51]]] = True
    x = rng.standard_normal((3, DIM)).astype(np.float32)
    qseg = np.array([0, 1, 2], np.int32)
    starts, lens, owners = (np.array(a, np.int32) for a in
                            ([0, 50], [50, 4], [0, 1]))
    none = np.zeros(0, np.int32)
    host, key = ops.pad_descriptor_batch(
        x, qseg, starts, lens, owners, none, none, none,
        np.zeros((0, DIM), np.float32), none)
    vals, gids = jax.device_get(ops.topk_segmented_desc(
        jax.device_put(vecs), jax.device_put(base_ids),
        jax.device_put(deleted), jax.device_put(host), key, K, impl=impl))
    assert vals.shape == gids.shape == (key[0], K) == (128, K)
    assert np.array_equal(gids == -1, np.isposinf(vals))
    assert (gids[2:] == -1).all()                # no candidates, padding
    for row, (lo, ln) in enumerate(zip(starts, lens)):
        cand = base_ids[lo:lo + ln]
        cand = cand[~deleted[cand]]
        dist = ((vecs[cand] - x[row]) ** 2).sum(1)
        want = cand[np.argsort(dist, kind="stable")[:K]]
        live = min(K, len(cand))
        assert gids[row, :live].tolist() == want.tolist()
        assert (gids[row, live:] == -1).all()
        np.testing.assert_allclose(vals[row, :live], np.sort(dist)[:K],
                                   rtol=1e-5, atol=1e-5)


def test_delta_tail_ships_rows_per_batch(dataset):
    """Inserts past the upload watermark ship ids + rows per batch (the
    bounded delta tail) while the frozen cover stays descriptor-resolved;
    results remain exact against brute force."""
    vecs, seqs = dataset
    vm = _vm(dataset, T=10 ** 9, auto_compact=False)
    vm.runtime.to_device()                      # freeze the watermark
    rng = np.random.default_rng(9)
    all_seqs = list(seqs)
    for s in ("abab", "cdcd"):
        vm.insert(rng.standard_normal(DIM).astype(np.float32), s)
        all_seqs.append(s)
    q = _queries(1, 4)[0]
    d, ids = vm.query(q, "ab", K)
    assert vm.runtime.traffic["row_bytes"] > 0
    want = [i for i, s in enumerate(all_seqs) if "ab" in s]
    dd = ((vm.vectors[want] - q) ** 2).sum(1)
    want = [want[j] for j in np.argsort(dd, kind="stable")[:K]]
    assert ids.tolist() == want


# --------------------------------------------------------------------- #
# fused multi-graph beams
# --------------------------------------------------------------------- #

def test_fused_vs_per_graph_parity(dataset):
    """Bucket-fused (graph, query) vmap must return exactly what the
    per-state launch loop returns (graph padding is unreachable)."""
    vm = _vm(dataset, T=5)                      # graph states on chains
    assert vm.stats()["hnsw_states"] > 0
    preds = ["a", "b", "ab", "a", "cd", "d"]
    q = _queries(len(preds), 5)
    res_fused = vm.query_batch(q, preds, K, ef_search=48)
    vm.runtime.fuse_graphs = False
    res_per = vm.query_batch(q, preds, K, ef_search=48)
    _assert_identical(res_fused, res_per, "fused-vs-per-graph")


def test_one_beam_launch_per_bucket(dataset):
    """Acceptance: beam launches per batch == graph buckets touched (not
    graph states, not (state, request) tuples)."""
    vm = _vm(dataset, T=5)
    preds = ["a", "b", "c", "d", "a", "b"]
    q = _queries(len(preds), 6)
    plan = vm.plan(preds)
    states = {u for e in plan.entries for s in e.sources
              for u in s.graph_states}
    assert len(states) > 1, "workload must touch several graph states"
    dev = vm.runtime.to_device()
    buckets = {dev["graph_slot"][u][0] for u in states}
    ops.reset_launch_stats()
    vm.query_batch(q, preds, K)
    stats = ops.launch_stats()
    assert stats.get("graph_fused", 0) == len(buckets)
    assert stats.get("graph_state", 0) == 0
    assert len(buckets) < len(states), \
        "bucketing degenerated to one bucket per state"


def test_tombstone_overfetch_clamped_to_beam_capacity(dataset):
    """Satellite fix: tombstones must never widen the beam past the
    ef-list capacity.  With |deleted| >> ef the executor switches to
    in-loop bitmap filtering and still fills k live results."""
    vm = _vm(dataset, T=5)
    ef = K + 4                                   # tiny beam capacity
    q = _queries(1, 7)[0]
    d0, i0 = vm.query(q, "a", K, ef_search=ef)
    victims = i0.tolist()
    for v in victims:
        vm.delete(v)                             # now k + |del| > ef_cap
    kk, ef_cap, bitmap = vm.runtime._graph_fetch_width(K, ef)
    assert bitmap and kk == K and ef_cap == ef
    d1, i1 = vm.query(q, "a", K, ef_search=ef)
    assert not set(victims) & set(i1.tolist())
    assert len(i1) == K                          # live slots fully filled
    with pytest.raises(ValueError, match="ef-list capacity"):
        from repro.core.hnsw_jax import hnsw_search_fused
        dev = vm.runtime.to_device()
        bkey = next(iter(dev["graph_buckets"]))
        b = dev["graph_buckets"][bkey]
        import jax.numpy as jnp
        hnsw_search_fused(dev["vectors"], b["ids"], b["level0"],
                          b["entry"], jnp.zeros(1, jnp.int32),
                          jnp.zeros((1, DIM), jnp.float32), k=16, ef=8)


# --------------------------------------------------------------------- #
# device-side merge
# --------------------------------------------------------------------- #

def test_device_merge_matches_host_merge_under_churn(dataset):
    """Bit-exactness on the churn oracle workload: the device dedup +
    top-k fold must equal the NumPy merge exactly — same ids, same f32
    distances — mid-delta and with tombstones."""
    vecs, seqs = dataset
    vm = _vm(dataset, T=10 ** 9, auto_compact=False)
    vm.runtime.to_device()
    rng = np.random.default_rng(11)
    for s in ("abca", "dcb", "abab"):
        vm.insert(rng.standard_normal(DIM).astype(np.float32), s)
    for v in (3, 17, 40):
        vm.delete(v)
    preds = PREDS + ["ab OR a", "NOT cd"]
    q = _queries(len(preds), 8)
    res_dev = vm.query_batch(q, preds, K)
    assert vm.runtime.device_merge
    vm.runtime.device_merge = False
    res_host = vm.query_batch(q, preds, K)
    _assert_identical(res_dev, res_host, "device-vs-host-merge")


def _uncertified(monkeypatch):
    """Every SQ8 certificate fails, so each batch escalates to fp32."""
    from repro.kernels import quant
    real = quant.topk_sq8_segmented_desc

    def uncertified(*args, **kwargs):
        v, g, cert = real(*args, **kwargs)
        return v, g, cert & False
    monkeypatch.setattr(quant, "topk_sq8_segmented_desc", uncertified)


# (predicates, VectorMatonConfig overrides, what is done to the index
# first): one upload, the scan and merge launches and one download per
# scan-only wave, against the NumPy merge of the same launch rows
DISPATCH_CASES = {
    "one_request": (["ab"], {}, None),
    "two_requests": (["a", "cd"], {}, None),
    "or_disjuncts": (["a OR cd", "ab OR ba OR dd"], {}, None),
    "tombstones": (["a", "ab OR cd"], {}, "delete"),
    "delta_tail": (["ab", "a OR cd"], {"auto_compact": False}, "insert"),
    "sq8_certified": (["a", "ab OR cd"], {"quantize": "sq8"}, None),
    "sq8_escalated": (["a", "ab OR cd"], {"quantize": "sq8"}, "uncertify"),
    "graph_and_scan": (["a", "abc", "ab OR cd"], {"T": 5}, None),
}


@pytest.mark.parametrize("case", sorted(DISPATCH_CASES))
def test_consolidated_dispatch_matches_host_merge(dataset, monkeypatch,
                                                  case):
    """The dispatch that ships a wave's inputs in one upload, cuts and
    cleans the scan's rows inside its program and hands a lone launch
    straight to the merge answers bit-for-bit as the NumPy host merge
    (``device_merge=False``), on waves of one and two requests, OR
    disjuncts, tombstones, a shipped delta tail, certified and
    escalated SQ8 batches, and a graph + scan multi-launch wave."""
    preds, cfg, prep = DISPATCH_CASES[case]
    vm = _vm(dataset, **{"T": 10 ** 9, **cfg})
    vm.runtime.to_device()
    rng = np.random.default_rng(21)
    if prep == "delete":
        for v in (2, 5, 11, 40, 41):
            vm.delete(v)
    elif prep == "insert":
        for s in ("abab", "cdab", "bab"):
            vm.insert(rng.standard_normal(DIM).astype(np.float32), s)
    elif prep == "uncertify":
        _uncertified(monkeypatch)
    q = _queries(len(preds), 12)
    stats0 = vm.maintenance_stats()
    res_dev = vm.query_batch(q, preds, K)
    stats1 = vm.maintenance_stats()
    vm.runtime.device_merge = False
    res_host = vm.query_batch(q, preds, K)
    _assert_identical(res_dev, res_host, case)
    if prep == "insert":
        assert stats1["traffic_row_bytes"] > stats0["traffic_row_bytes"]
    if cfg.get("quantize") == "sq8":
        got = "escalations" if prep == "uncertify" else "certified"
        assert stats1[f"sq8_{got}"] == stats0[f"sq8_{got}"] + 1
    if case == "graph_and_scan":
        kinds = vm.plan(preds).strategies
        assert vm.stats()["hnsw_states"] > 0 and kinds["chain"] > 0
        assert stats1["launch_graph_fused"] > stats0.get(
            "launch_graph_fused", 0)


@pytest.mark.parametrize("quantize,calls", [("none", 4), ("sq8", 5)])
def test_scan_only_wave_host_device_calls(dataset, quantize, calls):
    """A scan-only wave crosses between host and device at most five
    times (``traffic_host_device_calls``): one upload, the scan launch,
    the merge launch, one download, and with SQ8 the certificate's read
    back."""
    vm = _vm(dataset, T=10 ** 9, quantize=quantize)
    preds = ["a", "ab OR cd"]
    vm.query_batch(_queries(2, 13), preds, K)        # index upload
    before = vm.maintenance_stats()
    vm.query_batch(_queries(2, 14), preds, K)
    after = vm.maintenance_stats()
    assert after["sq8_certified"] == before["sq8_certified"] + (
        quantize == "sq8")
    got = (after["traffic_host_device_calls"]
           - before["traffic_host_device_calls"])
    assert got == calls <= 5


def test_residual_predicates_fall_back_to_host_merge(dataset):
    """Requests with host-side residual parts must keep merging on host
    (and stay correct) while pure device requests in the same batch use
    the device fold."""
    vm = _vm(dataset, T=10 ** 9)
    preds = ["a", "LIKE '%a%b%'", "ab"]
    q = _queries(len(preds), 9)
    res = vm.query_batch(q, preds, K)
    from repro.core.predicate import parse_predicate
    _, seqs = dataset
    for p, (d, ids) in zip(preds, res):
        pred = parse_predicate(p)
        assert all(pred.matches(seqs[i]) for i in ids.tolist()), p


# --------------------------------------------------------------------- #
# shape-bucketed launch cache
# --------------------------------------------------------------------- #

def test_retrace_bounded_across_batch_sweep(dataset):
    """Acceptance: a 20-shape steady-state sweep (batch sizes 1..20 over
    a rotating predicate mix) compiles at most O(#buckets) executables —
    counted both by the bucket-key counter and the jit caches."""
    vm = _vm(dataset, T=25)                      # mixed raw/graph chains
    ops.reset_launch_stats()
    cache0 = sum(v for v in ops.jit_cache_sizes().values() if v > 0)
    rng = np.random.default_rng(13)
    for size in range(1, 21):
        preds = [PREDS[(size + j) % len(PREDS)] for j in range(size)]
        q = rng.standard_normal((size, DIM)).astype(np.float32)
        vm.query_batch(q, preds, K)
    stats = ops.launch_stats()
    assert stats["launches"] >= 40               # the sweep did real work
    # every dimension is pow2-bucketed: a handful of executables serve
    # all 20 shapes (vs >= one per shape without bucketing)
    assert stats["executables"] <= 18, stats
    cache1 = sum(v for v in ops.jit_cache_sizes().values() if v > 0)
    assert cache1 - cache0 <= 18, ops.jit_cache_sizes()
    # steady state: replaying the sweep compiles NOTHING new
    before = ops.launch_stats()["retraces"]
    for size in range(1, 21):
        preds = [PREDS[(size + j) % len(PREDS)] for j in range(size)]
        q = rng.standard_normal((size, DIM)).astype(np.float32)
        vm.query_batch(q, preds, K)
    assert ops.launch_stats()["retraces"] == before
    assert sum(v for v in ops.jit_cache_sizes().values() if v > 0) == cache1


# --------------------------------------------------------------------- #
# SQ8 batched scan path
# --------------------------------------------------------------------- #

def test_sq8_single_segmented_launch(dataset):
    """The SQ8 scan path must route ALL scan items through ONE segmented
    quantized launch (it used to launch once per item) and keep recall
    against the fp32 executor."""
    vm_fp = _vm(dataset, T=10 ** 9)
    vm_q8 = _vm(dataset, T=10 ** 9, quantize="sq8")
    preds = ["a", "ab", "cd", "b", "a OR cd"]
    q = _queries(len(preds), 10)
    ops.reset_launch_stats()
    res_q8 = vm_q8.query_batch(q, preds, K)
    stats = ops.launch_stats()
    assert stats.get("sq8_scan", 0) == 1, stats
    res_fp = vm_fp.query_batch(q, preds, K)
    for (df, idf), (dq, idq), p in zip(res_fp, res_q8, preds):
        overlap = len(set(idf.tolist()) & set(idq.tolist())) / len(idf)
        assert overlap >= 0.8, (p, idf, idq)
    # rerank distances are exact fp32
    vecs, _ = dataset
    for r, (dq, idq) in enumerate(res_q8):
        for dist, gid in zip(dq.tolist(), idq.tolist()):
            diff = q[r] - vecs[gid]
            assert abs(float(diff @ diff) - dist) < 1e-2
