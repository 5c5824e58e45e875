"""Pipelined serving executor (DESIGN.md §7).

The contract under test: the pipelined batcher — planning wave N+1 on a
background thread, dispatching it while wave N executes, fetching N
after N+1 is in flight — returns BIT-EXACT results vs the synchronous
oracle (``pipeline=False``) for identical op streams, on both backends,
including streams with interleaved inserts/deletes/compactions and a
mid-pipeline generation swap that forces a staleness replan.  On top:
thread-safe submission (no dropped or crossed request ids), weighted
deficit-round-robin tenant admission, bounded ``drain``, and the
pipeline observability counters.
"""

import threading

import numpy as np
import pytest

from repro.core.predicate import parse_predicate
from repro.core.vectormaton import VectorMatonConfig
from repro.serve.batching import ContinuousBatcher, DrainTimeout
from repro.serve.engine import Request, RetrievalEngine
from repro.serve.step import StagingRing

DIM = 12
ALPHA = "abcd"
PREDS = ["ab", "cd", "a", "ab AND cd", "ab OR cd", "NOT ab",
         "LIKE '%a%b%'", "ab AND NOT cd"]


def _mk(rng, n):
    seqs = ["".join(rng.choice(list(ALPHA), size=rng.integers(4, 12)))
            for _ in range(n)]
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    return vecs, seqs


def _engine(backend, n=150, seed=0, **cfg):
    rng = np.random.default_rng(seed)
    vecs, seqs = _mk(rng, n)
    return RetrievalEngine(
        vecs, seqs, VectorMatonConfig(T=20, M=8, ef_con=40,
                                      backend=backend, **cfg))


def _requests(rng, count, tenants=1):
    return [Request(vector=rng.standard_normal(DIM).astype(np.float32),
                    pattern=PREDS[i % len(PREDS)], k=5,
                    tenant="t%d" % (i % tenants))
            for i in range(count)]


def _snap(res, tickets):
    return {t: (res[t].ids.tolist(),
                np.round(res[t].distances, 5).tolist())
            for t in tickets}


# --------------------------------------------------------------------- #
# read-only parity + overlap counters
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_pipeline_read_parity(backend):
    """A pure-read stream through the pipelined batcher is bit-exact vs
    the synchronous oracle, and the pipeline actually ran (waves counted,
    no replans needed without writes)."""
    rng = np.random.default_rng(1)
    reqs = _requests(rng, 48)
    outs = {}
    for mode in (False, True):
        eng = _engine(backend)
        b = ContinuousBatcher(eng, budget=10 ** 9, max_wave=8,
                              pipeline=mode)
        tickets = [b.submit(r) for r in reqs]
        res = b.drain()
        outs[mode] = _snap(res, tickets)
        if mode:
            stats = b.maintenance_stats()
            assert stats["pipeline_waves"] >= 6
            assert stats["pipeline_replans"] == 0
            assert "device_idle_ms" in stats
            assert "planner_wait_ms" in stats
            b.close()
    assert outs[False] == outs[True]


def test_pipelining_keeps_launches_per_wave():
    """Pipelining reorders waves, never what they launch: the same
    stream of waves makes as many device launches of each kind through
    the pipelined executor as through the synchronous loop."""
    from repro.kernels import ops
    rng = np.random.default_rng(4)
    reqs = _requests(rng, 48)
    launches = {}
    for mode in (False, True):
        eng = _engine("jax", plan_mode="static")
        b = ContinuousBatcher(eng, budget=10 ** 9, max_wave=8,
                              pipeline=mode)
        for r in reqs:
            b.submit(r)
        ops.reset_launch_stats()
        b.drain()
        launches[mode] = {kind: n for kind, n in ops.launch_stats().items()
                          if kind not in ("retraces", "executables")}
        b.close()
    assert launches[False]["launches"] >= 6
    assert launches[False] == launches[True]


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_pipeline_churn_parity(backend):
    """Inserts + deletes + compactions streamed through the pipelined
    batcher: write barriers + staleness replans keep every response
    bit-exact vs the synchronous loop over the same op script."""
    rng = np.random.default_rng(7)
    reqs = _requests(rng, 40)
    ins = [(rng.standard_normal(DIM).astype(np.float32),
            "".join(rng.choice(list(ALPHA), size=8))) for _ in range(6)]
    outs = {}
    for mode in (False, True):
        eng = _engine(backend, auto_compact=False)
        b = ContinuousBatcher(eng, budget=10 ** 9, max_wave=4,
                              pipeline=mode)
        tickets = []
        wt = []
        # interleave: 8 reads, write, 8 reads, delete, ... compaction
        for i, r in enumerate(reqs):
            tickets.append(b.submit(r))
            if i % 8 == 7 and i // 8 < len(ins):
                v, s = ins[i // 8]
                wt.append(b.submit_insert(v, s))
            if i == 19:
                wt.append(b.submit_delete(3))
            if i == 27:
                wt.append(b.submit_compact())
        res = b.drain()
        outs[mode] = _snap(res, tickets)
        assert all(t in b.write_results for t in wt)
        if mode:
            b.close()
    assert outs[False] == outs[True]


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_pipeline_replan_on_generation_swap(backend):
    """A write injected BETWEEN a wave's plan and its dispatch (the
    ``on_wave_start`` hook fires at exactly that point in pipelined mode)
    must be staleness-rejected and replanned — and the replanned results
    must equal the oracle, which sees the same write land before the
    same wave plans."""
    rng = np.random.default_rng(11)
    reqs = _requests(rng, 24)
    wvec = rng.standard_normal(DIM).astype(np.float32)
    outs = {}
    for mode in (False, True):
        eng = _engine(backend, auto_compact=False)
        b = ContinuousBatcher(eng, budget=10 ** 9, max_wave=6,
                              pipeline=mode)
        fired = []

        # both modes run the identical index mutation at the identical
        # observable point (just before wave-job 2 plans/dispatches):
        # the oracle sees it before planning, the pipeline is forced to
        # staleness-reject and replan — same final plan either way
        def hook(idx):
            if idx == 2 and not fired:
                fired.append(idx)
                eng.insert(wvec, "abab")       # direct: bumps delta
                eng.compact()                  # and swaps the generation

        b.on_wave_start = hook
        tickets = [b.submit(r) for r in reqs]
        res = b.drain()
        outs[mode] = _snap(res, tickets)
        assert fired == [2]
        if mode:
            assert b.maintenance_stats()["pipeline_replans"] >= 1
            b.close()
    assert outs[False] == outs[True]


def test_pipeline_replan_results_are_fresh():
    """After a replan the answers include the inserted vector when it
    qualifies — proof the replanned wave executed against the NEW state,
    not a resurrected stale plan."""
    rng = np.random.default_rng(3)
    eng = _engine("numpy", n=60, auto_compact=False)
    b = ContinuousBatcher(eng, budget=10 ** 9, max_wave=4, pipeline=True)
    probe = rng.standard_normal(DIM).astype(np.float32)
    done = []

    def hook(idx):
        if idx == 1 and not done:
            done.append(idx)
            eng.insert(probe, "abab")   # identical vector => distance 0

    b.on_wave_start = hook
    tickets = []
    for i in range(12):
        tickets.append(b.submit(Request(vector=probe, pattern="ab", k=3)))
    res = b.drain()
    b.close()
    assert b.maintenance_stats()["pipeline_replans"] >= 1
    new_id = len(eng.index.sequences) - 1
    # every wave from the replanned one on must rank the new exact-match
    # vector first
    late = [t for t in tickets[4:]]
    for t in late:
        assert res[t].ids[0] == new_id


# --------------------------------------------------------------------- #
# thread safety
# --------------------------------------------------------------------- #

def test_concurrent_submitters_no_drops():
    """8 submitter threads × reads+writes against one pipelined batcher:
    every ticket gets a response, every response is exact for its own
    request (no crossed wires), every write ticket resolves."""
    rng = np.random.default_rng(17)
    eng = _engine("numpy", n=120)
    b = ContinuousBatcher(eng, budget=10 ** 9, max_wave=16, pipeline=True)
    seqs_snapshot = list(eng.index.sequences)
    n_threads, per = 8, 12
    tickets = [[] for _ in range(n_threads)]
    reqs = [[] for _ in range(n_threads)]
    barrier = threading.Barrier(n_threads)

    def submitter(ti):
        trng = np.random.default_rng(100 + ti)
        barrier.wait()
        for j in range(per):
            r = Request(
                vector=trng.standard_normal(DIM).astype(np.float32),
                pattern=PREDS[(ti + j) % len(PREDS)], k=5,
                tenant="t%d" % ti)
            reqs[ti].append(r)
            tickets[ti].append(b.submit(r))

    threads = [threading.Thread(target=submitter, args=(ti,))
               for ti in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    res = b.drain()
    b.close()
    assert len(res) == n_threads * per          # nothing dropped
    for ti in range(n_threads):
        for r, tk in zip(reqs[ti], tickets[ti]):
            d, ids = eng.query_batch(r.vector[None, :], [r.pattern],
                                     r.k)[0]
            assert res[tk].ids.tolist() == ids.tolist()
            pred = parse_predicate(r.pattern)
            assert all(pred.matches(seqs_snapshot[i])
                       for i in res[tk].ids.tolist())


def test_concurrent_submit_with_writes_exact():
    """Submitters race a writer thread; after drain, results for every
    ticket must match a per-request re-query of the final index state
    when re-served (sanity: no torn plans, no exceptions), and all write
    tickets resolve to live ids."""
    eng = _engine("numpy", n=100, seed=4)
    b = ContinuousBatcher(eng, budget=10 ** 9, max_wave=8, pipeline=True)
    stop = threading.Event()
    wtickets = []

    def writer():
        wrng = np.random.default_rng(55)
        for _ in range(5):
            wtickets.append(b.submit_insert(
                wrng.standard_normal(DIM).astype(np.float32),
                "".join(wrng.choice(list(ALPHA), size=6))))

    def reader(out):
        rrng = np.random.default_rng(66)
        for j in range(10):
            out.append(b.submit(Request(
                vector=rrng.standard_normal(DIM).astype(np.float32),
                pattern=PREDS[j % len(PREDS)], k=4)))

    rt1, rt2 = [], []
    ts = [threading.Thread(target=writer),
          threading.Thread(target=reader, args=(rt1,)),
          threading.Thread(target=reader, args=(rt2,))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    res = b.drain()
    b.close()
    for tk in rt1 + rt2:
        assert tk in res and len(res[tk].ids) > 0
    for wt in wtickets:
        assert wt in b.write_results


# --------------------------------------------------------------------- #
# tenant admission (weighted deficit round-robin)
# --------------------------------------------------------------------- #

def test_tenant_fairness_no_starvation():
    """Tenant A floods 60 requests before tenant B's 6 arrive; DRR must
    interleave B into early waves instead of serving all of A first."""
    rng = np.random.default_rng(9)
    eng = _engine("numpy", n=100)
    b = ContinuousBatcher(eng, budget=10 ** 9, max_wave=8, pipeline=False)
    for i in range(60):
        b.submit(Request(vector=rng.standard_normal(DIM)
                         .astype(np.float32),
                         pattern="ab", k=3, tenant="flood"))
    b_tickets = [b.submit(Request(vector=rng.standard_normal(DIM)
                                  .astype(np.float32),
                                  pattern="cd", k=3, tenant="quiet"))
                 for _ in range(6)]
    first_three = []
    for _ in range(3):
        first_three.extend(b.run_wave().keys())
    assert any(t in first_three for t in b_tickets), \
        "quiet tenant starved out of the first three waves"
    b.drain()
    st = b.tenant_stats()
    assert st["quiet"]["served"] == 6
    assert st["flood"]["served"] == 60
    assert st["quiet"]["p50_ms"] >= 0.0


def test_tenant_weights_shift_share():
    """With weight 3:1 the heavy tenant takes a proportionally larger
    slice of each budget-bound wave."""
    rng = np.random.default_rng(13)
    eng = _engine("numpy", n=100)
    # budget ≈ a few requests per wave: force contention
    cost_probe = eng.index.compile("a").est
    b = ContinuousBatcher(eng, budget=int(cost_probe * 4.5), max_wave=64,
                          pipeline=False,
                          tenant_weights={"heavy": 3.0, "light": 1.0})
    for i in range(24):
        b.submit(Request(vector=rng.standard_normal(DIM)
                         .astype(np.float32), pattern="a", k=3,
                         tenant="heavy" if i % 2 == 0 else "light"))
    wave = b.next_wave()
    heavy = sum(1 for q in wave if q.request.tenant == "heavy")
    light = sum(1 for q in wave if q.request.tenant == "light")
    assert heavy > light
    b.drain()                                    # everyone still finishes
    assert b.pending() == 0


def test_single_tenant_admission_unchanged():
    """One tenant => the legacy strict-FIFO budget walk, byte for byte:
    stop at the first over-budget head, tick only that head."""
    rng = np.random.default_rng(2)
    eng = _engine("numpy", n=80)
    cost = eng.index.compile("a").est
    b = ContinuousBatcher(eng, budget=int(cost * 2.5), max_wave=64,
                          pipeline=False)
    for _ in range(7):
        b.submit(Request(vector=rng.standard_normal(DIM)
                         .astype(np.float32), pattern="a", k=3))
    w1 = b.next_wave()
    assert len(w1) == 2                      # 2 fit, 3rd head deferred
    assert len(b._deferred) == 1
    w2 = b.next_wave()
    assert len(w2) == 2
    assert w2[0].seq == 2                    # deferred head goes first


# --------------------------------------------------------------------- #
# drain bounds + staging ring
# --------------------------------------------------------------------- #

def test_drain_max_waves_raises():
    rng = np.random.default_rng(21)
    eng = _engine("numpy", n=60)
    b = ContinuousBatcher(eng, budget=1, max_wave=1, max_defer=0,
                          pipeline=False)
    for _ in range(30):
        b.submit(Request(vector=rng.standard_normal(DIM)
                         .astype(np.float32), pattern="a", k=2))
    with pytest.raises(DrainTimeout):
        b.drain(max_waves=3)
    assert b.pending() == 27                 # 3 waves × 1 admitted


def test_drain_deadline_raises():
    rng = np.random.default_rng(22)
    eng = _engine("numpy", n=60)
    b = ContinuousBatcher(eng, budget=10 ** 9, max_wave=1,
                          pipeline=False)
    for _ in range(50):
        b.submit(Request(vector=rng.standard_normal(DIM)
                         .astype(np.float32), pattern="a", k=2))
    with pytest.raises(DrainTimeout):
        b.drain(deadline_s=0.0)


def test_drain_unbounded_still_completes():
    rng = np.random.default_rng(24)
    eng = _engine("numpy", n=60)
    b = ContinuousBatcher(eng, budget=10 ** 9, max_wave=4, pipeline=True)
    tks = [b.submit(Request(vector=rng.standard_normal(DIM)
                            .astype(np.float32), pattern="ab", k=2))
           for _ in range(10)]
    res = b.drain(max_waves=100, deadline_s=60.0)
    b.close()
    assert all(t in res for t in tks)


def test_staging_ring_reuse_and_growth():
    ring = StagingRing(dim=4, capacity=2, slots=2)
    a = ring.acquire(np.ones((2, 4), np.float32))
    bb = ring.acquire(np.full((5, 4), 2.0, np.float32))   # forces growth
    assert ring.grows == 1
    assert a.view().shape == (2, 4)
    assert bb.view().shape == (5, 4)
    assert float(bb.view()[0, 0]) == 2.0
    # both slots leased: a third acquire must time out...
    with pytest.raises(TimeoutError):
        ring.acquire(np.zeros((1, 4), np.float32), timeout=0.05)
    a.release()
    a.release()                                  # idempotent
    c = ring.acquire(np.zeros((1, 4), np.float32), timeout=1.0)
    assert c.view().shape == (1, 4)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_engine_stage_api_matches_query_batch(backend):
    """plan/dispatch/fetch composed manually equals query_batch."""
    rng = np.random.default_rng(31)
    eng = _engine(backend, n=90)
    q = rng.standard_normal((6, DIM)).astype(np.float32)
    pats = PREDS[:6]
    ref = eng.query_batch(q, pats, 4)
    wave = eng.plan_batch(q, pats, 4)
    pending = eng.dispatch_batch(wave)
    got = eng.fetch_batch(pending)
    for (d0, i0), (d1, i1) in zip(ref, got):
        assert i0.tolist() == i1.tolist()
        np.testing.assert_allclose(d0, d1, rtol=1e-6)


def test_stale_wave_plan_rejected_at_dispatch():
    """The PR 3 staleness stamp carries through the stage API: a write
    between plan_batch and dispatch_batch raises, it does not silently
    serve a torn snapshot."""
    rng = np.random.default_rng(33)
    eng = _engine("numpy", n=70, auto_compact=False)
    q = rng.standard_normal((2, DIM)).astype(np.float32)
    wave = eng.plan_batch(q, ["ab", "cd"], 3)
    eng.insert(rng.standard_normal(DIM).astype(np.float32), "abcd")
    with pytest.raises(ValueError, match="stale plan"):
        eng.dispatch_batch(wave)


# --------------------------------------------------------------------- #
# typed deadline errors: RequestTimeout, StagingStall
# --------------------------------------------------------------------- #

def test_request_timeout_typed_and_counted():
    """A wave that never delivers must surface as a typed
    ``RequestTimeout`` carrying the undelivered tickets — with the drop
    recorded against the tenant — instead of hanging the submitter on
    the old hard-coded 120 s wait."""
    from repro.serve.batching import RequestTimeout
    from repro.serve.pipeline import WaveJob

    rng = np.random.default_rng(0)
    b = ContinuousBatcher(_engine("numpy"), pipeline=False,
                          request_timeout_s=0.05)
    try:
        t0 = b.submit(Request(
            vector=rng.standard_normal(DIM).astype(np.float32),
            pattern="ab", k=3, tenant="slow"))
        t1 = b.submit(Request(
            vector=rng.standard_normal(DIM).astype(np.float32),
            pattern="cd", k=3, tenant="slow"))
        items = b.next_wave()
        assert [q.seq for q in items] == [t0, t1]
        wedged = WaveJob(queries=np.zeros((2, DIM), np.float32),
                         patterns=["ab", "cd"], k=3, ef_search=64)
        with pytest.raises(RequestTimeout) as ei:
            b._collect_jobs([(wedged, items)], {})
        assert ei.value.tickets == [t0, t1]
        assert isinstance(ei.value, RuntimeError)
        assert b.tenant_stats()["slow"]["dropped"] == 2
        assert b.tenant_stats()["slow"]["served"] == 0
    finally:
        b.close()


def test_request_timeout_config_plumbs_through():
    b = ContinuousBatcher(_engine("numpy"), request_timeout_s=7.5)
    try:
        assert b.request_timeout_s == 7.5
        assert "dropped" in next(iter(
            b.tenant_stats().values()), {"dropped": 0})
    finally:
        b.close()


def test_staging_stall_typed_with_diagnostics():
    """All slots leased past the deadline -> typed ``StagingStall`` (a
    ``TimeoutError`` subclass, so legacy handlers still catch it)
    carrying the ring depth and observed wait, and counted on the
    ring."""
    from repro.serve.step import StagingStall

    ring = StagingRing(dim=4, slots=2)
    a = ring.acquire(np.zeros((1, 4), np.float32))
    b = ring.acquire(np.zeros((1, 4), np.float32))
    with pytest.raises(StagingStall) as ei:
        ring.acquire(np.zeros((1, 4), np.float32), timeout=0.05)
    err = ei.value
    assert isinstance(err, TimeoutError)
    assert err.depth == 2
    assert err.wait_ms >= 50.0
    assert ring.stalls == 1
    assert "2 upload slots" in str(err)
    a.release()
    # a freed slot unwedges the ring
    c = ring.acquire(np.zeros((1, 4), np.float32), timeout=0.05)
    c.release()
    b.release()
    assert ring.stalls == 1
