"""The resident table in segment order and the descriptor scan's
in-place form (DESIGN.md §2, §3).

``PackedRuntime`` uploads its table in position space: each row at the
smallest segment that holds it, so in a runtime where each row carries
one label every label's segment is one run of consecutive positions.
A launch whose descriptors are all such runs, with no tail, in a
runtime without chains, reads them where they lie (the in-place form);
every other launch gathers its candidates first (the gather form).
Both forms must give the same answers, and every answer must come back
as a row id, not a position.
"""

import numpy as np
import pytest

from repro.core.vectormaton import VectorMaton, VectorMatonConfig
from repro.kernels import ops

DIM = 16
K = 10
LABELS = "ABCDEFGHIJKL"


@pytest.fixture(scope="module")
def labelled():
    rng = np.random.default_rng(1017)
    n = 3000
    seqs = [LABELS[i] for i in rng.integers(0, len(LABELS), n)]
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    return vecs, seqs


def _vm(data, **kw):
    vecs, seqs = data
    kw.setdefault("backend", "jax")
    kw.setdefault("T", 10 ** 9)
    kw.setdefault("quantize", "none")
    return VectorMaton(vecs, seqs, VectorMatonConfig(**kw))


def _queries(n, seed):
    return np.random.default_rng(seed).standard_normal(
        (n, DIM)).astype(np.float32)


def _brute(vecs, seqs, deleted, q, pred, k=K):
    """Row ids of the k nearest live rows holding one of ``pred``'s
    labels (``"A"`` or ``"A OR B"``), by ``ops.topk_numpy``."""
    want = set(pred.split(" OR "))
    cand = np.asarray([i for i, s in enumerate(seqs)
                       if s in want and i not in deleted], np.int64)
    d, li = ops.topk_numpy(q[None], vecs[cand], min(k, len(cand)))
    return d[0], cand[li[0]]


def _both_forms(vm, q, preds, impl):
    """One wave of ``preds`` assembled and uploaded once, then scanned
    in the in-place and the gather form: their (vals, row ids)."""
    rt = vm.runtime
    plan = vm.plan(preds)
    scan_items = rt._gather_work(plan)[0]
    batch = rt._assemble_scan_batch(q, scan_items)
    assert batch.runs is not None
    dev_batch, key, _ = rt._upload_scan_batch(q, batch, False)
    dev = rt.to_device()
    out = []
    for inplace in (True, False):
        v, g = ops.topk_segmented_desc(
            dev["vectors"], dev["base_ids"], dev["deleted"], dev_batch, key,
            K, impl=impl, inplace=inplace)
        out.append((np.asarray(v)[:len(batch.q_rows)],
                    rt.row_ids(np.asarray(g)[:len(batch.q_rows)])))
    return batch, key, out


def _same_up_to_ties(a, b):
    (va, ia), (vb, ib) = a, b
    np.testing.assert_allclose(va, vb, rtol=1e-6, atol=1e-6)
    for r in range(len(ia)):
        for c in np.flatnonzero(ia[r] != ib[r]):
            near = np.abs(va[r] - va[r, c]) <= 1e-5 * max(1.0, va[r, c])
            assert near.sum() > 1, (r, c, ia[r], ib[r])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case", ["one", "several", "deleted"])
def test_inplace_matches_gather(labelled, impl, case):
    """The two forms of one uploaded wave agree, for one request or
    several (one of two descriptors), with rows tombstoned inside a
    segment, at segments that start and end off a block boundary, with
    padding columns in the grid."""
    vecs, seqs = labelled
    vm = _vm(labelled)
    preds = {"one": ["C"],
             "several": ["A", "B", "K", "A OR L", "B"],
             "deleted": ["D", "E"]}[case]
    q = _queries(len(preds), 5)
    if case == "deleted":
        for r, p in enumerate(preds):
            for v in _brute(vecs, seqs, set(), q[r], p, 4)[1]:
                vm.delete(int(v))
    batch, key, (inplace, gather) = _both_forms(vm, q, preds, impl)
    _same_up_to_ties(inplace, gather)
    bn = ops.desc_tiles(key, K)[1]
    ends = batch.runs + batch.lens
    assert np.any(batch.runs % bn) and np.any(ends % bn)   # off a boundary
    cols = ops.inplace_columns(batch.runs, batch.lens, key, K)
    assert cols < key[1] + 2 * bn * key[4]                 # padding columns
    rt = vm.runtime
    for r, row in enumerate(batch.q_rows):     # against the row's own runs
        mine = batch.owners == batch.q_owner[r]
        cand = np.concatenate([rt.base_ids[s:s + n] for s, n in
                               zip(batch.starts[mine], batch.lens[mine])])
        cand = cand[~np.isin(cand, list(vm.deleted))]
        d, li = ops.topk_numpy(q[row][None], vecs[cand], K)
        assert inplace[1][r].tolist() == cand[li[0]].tolist()
        np.testing.assert_allclose(inplace[0][r], d[0], rtol=1e-5,
                                   atol=1e-5)


def test_label_segments_are_runs(labelled):
    """The table order is a permutation, and every label's segment lies
    at consecutive positions, in id order."""
    vm = _vm(labelled)
    rt = vm.runtime
    n = len(labelled[0])
    assert np.array_equal(np.sort(rt.perm), np.arange(n))
    assert np.array_equal(rt.positions(rt.perm), np.arange(n))
    assert np.array_equal(rt.row_ids(rt.positions(np.arange(n))),
                          np.arange(n))
    for label in LABELS:
        lo, hi = rt.chain_cover(vm.plan([label]).entries[0].state
                                ).raw_segments[0]
        ids = rt.base_ids[lo:hi]
        pos = rt.positions(ids)
        assert np.array_equal(pos, pos[0] + np.arange(hi - lo)), label
        assert np.all(np.diff(ids) > 0)
        assert rt._desc_runs(np.array([lo]), np.array([hi - lo])) == pos[0]
    dev = rt.to_device()
    assert np.array_equal(np.asarray(dev["vectors"]),
                          labelled[0][rt.perm])


@pytest.mark.parametrize("quantize", ["none", "sq8"])
def test_answers_are_row_ids(labelled, quantize):
    """Through ``VectorMaton.query`` the answers are row ids, equal to
    the brute force of ``ops.topk_numpy`` over the label's live rows."""
    vecs, seqs = labelled
    vm = _vm(labelled, quantize=quantize)
    vm.delete(11)
    q = _queries(4, 9)
    for r, pred in enumerate(["A", "F", "L", "G OR H"]):
        d, ids = vm.query(q[r], pred, K)
        wd, wids = _brute(vecs, seqs, vm.deleted, q[r], pred)
        assert ids.tolist() == wids.tolist(), pred
        np.testing.assert_allclose(d, wd, rtol=1e-5, atol=1e-5)


def _count_forms(monkeypatch):
    seen = {True: 0, False: 0}
    launch = ops.topk_segmented_desc

    def counted(*args, inplace=False, **kw):
        seen[inplace] += 1
        return launch(*args, inplace=inplace, **kw)
    monkeypatch.setattr(ops, "topk_segmented_desc", counted)
    return seen


def test_tail_and_chains_take_the_gather_form(labelled, monkeypatch):
    """A launch with a resident tail, and every launch of a runtime with
    chains (rows with protein-like sequences of their own), gathers; the
    counter counts exactly the in-place launches."""
    vecs, seqs = labelled
    seen = _count_forms(monkeypatch)
    vm = _vm(labelled)
    q = _queries(3, 21)
    vm.query_batch(q, ["A", "B", "C"], K)
    assert seen == {True: 1, False: 0}
    # an insert before the table uploads lands below the watermark: a
    # resident tail
    vm2 = _vm(labelled)
    vm2.insert(q[0], "A")
    d, ids = vm2.query(q[0], "A", K)
    assert ids[0] == len(vecs) and seen == {True: 1, False: 1}
    assert (vm2.maintenance_stats()["traffic_scan_inplace_launches"]
            == 0)
    rng = np.random.default_rng(3)
    prot = ["".join(rng.choice(list("ACDEFGHIKLMNPQRSTVWY"),
                               rng.integers(20, 60))) for _ in range(400)]
    pv = rng.standard_normal((400, DIM)).astype(np.float32)
    vm3 = VectorMaton(pv, prot, VectorMatonConfig(
        backend="jax", T=10 ** 9, quantize="none"))
    assert vm3.runtime.chains and vm3.runtime._run_breaks is None
    vm3.query_batch(_queries(3, 4), ["GK", "L", "RGD"], K)
    st = vm3.maintenance_stats()
    assert seen[True] == 1 and seen[False] >= 2
    assert st["traffic_scan_inplace_launches"] == 0
    assert st["traffic_scan_launches"] >= 1
    st = vm.maintenance_stats()
    assert st["traffic_scan_inplace_launches"] == 1
    assert st["traffic_scan_launches"] == 1


def test_inplace_counter_counts_inplace_launches(labelled, monkeypatch):
    """Over waves of labels, of OR unions and with deletes, the counter
    matches the in-place launches made, and the in-place pair count is
    the columns its blocks cover."""
    seen = _count_forms(monkeypatch)
    vm = _vm(labelled)
    waves = [["A"], ["B", "C"], ["D OR E", "F"], ["G", "G", "H"]]
    for i, preds in enumerate(waves):
        vm.query_batch(_queries(len(preds), 30 + i), preds, K)
        if i == 1:
            vm.delete(5)
    st = vm.maintenance_stats()
    assert seen == {True: len(waves), False: 0}
    assert st["traffic_scan_inplace_launches"] == len(waves)
    assert st["traffic_scan_launches"] == len(waves)
    assert st["traffic_scan_pairs_computed"] > st["traffic_scan_pairs_needed"]
