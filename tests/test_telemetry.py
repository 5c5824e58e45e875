"""Spans and counters of the served path (DESIGN.md §7).

A short profiler window over a pipelined ``ContinuousBatcher`` whose
queue never empties, read back through the benchmark's own trace reader
(``bench/tracefile.py``): every stage span on its thread with the
wave's id, children inside their parents, and the planner and executor
threads inside some span nearly all the time.  Then the counters, each
against what it should count: scan pairs from the launch's shapes,
admissions per request, compiles per new shape, and the three parts of
the former mixed merge timer.
"""

from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core.vectormaton import VectorMatonConfig
from repro.kernels import ops
from repro.kernels.tuning import MAX_BLOCK_N
from repro.serve import telemetry
from repro.serve.batching import ContinuousBatcher
from repro.serve.engine import Request, RetrievalEngine

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import tracefile  # noqa: E402

DIM = 16
PATTERNS = ["ab", "cd", "a", "bc", "LIKE 'a%d'"]

# (thread, stage) of every span, and the parent each child nests in
SPANS = {
    ("bench-client", "submit"), ("bench-client", "engine_lock"),
    ("bench-client", "compile_predicate"),
    ("bench-server", "admit"), ("bench-server", "collect"),
    ("bench-server", "deliver"),
    ("repro-planner", "plan_batch"), ("repro-planner", "stage_queries"),
    ("repro-planner", "handoff"),
    ("repro-executor", "await_plan"), ("repro-executor", "dispatch_batch"),
    ("repro-executor", "assemble"), ("repro-executor", "launch_scan"),
    ("repro-executor", "launch_merge"), ("repro-executor", "fetch_batch"),
    ("repro-executor", "sync"), ("repro-executor", "merge_host"),
    ("repro-executor", "verify_residual"), ("repro-executor", "finish"),
}
PARENT = {"engine_lock": "submit", "compile_predicate": "submit",
          "assemble": "dispatch_batch", "launch_scan": "dispatch_batch",
          "launch_merge": "dispatch_batch", "sync": "fetch_batch",
          "merge_host": "fetch_batch", "verify_residual": "fetch_batch"}
# the index build's phases, each named as it is (no thread of its own)
BUILD_SPANS = {("build", "esam"), ("build", "state_indexes"),
               ("build", "runtime")}
STAGES = tuple(sorted({stage for _, stage in SPANS | BUILD_SPANS}))


def _corpus(n, seed=0):
    rng = np.random.default_rng(seed)
    seqs = ["".join(rng.choice(list("abcd"), size=6)) for _ in range(n)]
    return rng.standard_normal((n, DIM)).astype(np.float32), seqs


def _engine(n=4000, **cfg):
    vecs, seqs = _corpus(n)
    return RetrievalEngine(vecs, seqs, VectorMatonConfig(
        T=10 ** 9, backend="jax", **cfg))


def _request(rng, i):
    return Request(vector=rng.standard_normal(DIM).astype(np.float32),
                   pattern=PATTERNS[i % len(PATTERNS)], k=5)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler window of 1.5 s over a batcher kept full by
    a sender thread and drained by a serving thread; returns the
    ``.xplane.pb`` path."""
    import jax
    eng = _engine()
    b = ContinuousBatcher(eng, max_wave=4, budget=10 ** 9)
    rng = np.random.default_rng(1)
    for i in range(40):                         # warm every shape
        b.submit(_request(rng, i))
    b.drain()
    stop = threading.Event()

    def send():
        i = 0
        while not stop.is_set():
            while b.pending() < 40:
                b.submit(_request(rng, i))
                i += 1
            time.sleep(0.005)

    def serve():
        while not stop.is_set():
            b.drain()

    for i in range(40):
        b.submit(_request(rng, i))
    threads = [threading.Thread(target=send, name="bench-client"),
               threading.Thread(target=serve, name="bench-server")]
    for t in threads:
        t.start()
    out = tmp_path_factory.mktemp("trace")
    try:
        time.sleep(0.3)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(out), profiler_options=opts)
        time.sleep(1.5)
        jax.profiler.stop_trace()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        b.close()
    assert not any(t.is_alive() for t in threads)
    return tracefile.find_xplane(str(out))


def _host_events(path):
    """``(thread, stage, start, end, metadata)`` of the program's spans."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                thread, _, stage = ev.name.rpartition("/")
                if stage in STAGES and thread:
                    out.append((thread, stage, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)))
    return out


def test_build_phase_spans_in_a_traced_build(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        _engine(n=300)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tracefile.find_xplane(str(tmp_path)))
    assert {(t, s) for t, s, *_ in events} == BUILD_SPANS


def test_every_stage_span_on_its_thread_with_a_wave_id(traced):
    events = _host_events(traced)
    assert {(t, s) for t, s, *_ in events} == SPANS
    assert all(isinstance(m.get("wave"), int) for *_, m in events)
    submits = [m for t, s, _, _, m in events if s == "submit"]
    assert submits and all(isinstance(m.get("ticket"), int)
                           for m in submits)
    # the pipeline's stages of one wave share its id across threads
    waves = {s: {m["wave"] for _, st, _, _, m in events if st == s}
             for s in ("plan_batch", "dispatch_batch", "fetch_batch",
                       "collect")}
    common = set.intersection(*waves.values())
    assert len(common) >= 5


def _recorded_window(raw):
    """From the first span the tracer recorded to the last: the host
    tracer starts recording some time after ``start_trace`` returns
    (longer on a loaded machine), and keeps only the spans that begin
    and end while it records."""
    return (min(a for _, a, _ in raw["spans"]),
            max(b for _, _, b in raw["spans"]))


def test_child_spans_nest_inside_their_parents(traced):
    events = _host_events(traced)
    lo, hi = _recorded_window(tracefile.read(traced, stages=STAGES))
    parents = {}
    for t, s, a, b, m in events:
        parents.setdefault((t, s, m["wave"]), []).append((a, b))
    edge = 100_000_000      # a parent begun before the window is missing
    checked = 0
    for t, s, a, b, m in events:
        if s not in PARENT or a < lo + edge or b > hi - edge:
            continue
        around = parents.get((t, PARENT[s], m["wave"]), [])
        assert any(pa <= a and b <= pb for pa, pb in around), (t, s, m)
        checked += 1
    assert checked >= 50


def test_planner_and_executor_spans_cover_the_window(traced):
    raw = tracefile.read(traced, stages=STAGES)
    lo, hi = _recorded_window(raw)
    iv = np.asarray([[a, b] for name, a, b in raw["spans"]
                     if name.split("/")[0] in ("repro-planner",
                                               "repro-executor")])
    covered = sum(b - a for a, b in tracefile.union(np.clip(iv, lo, hi)))
    assert covered >= 0.95 * (hi - lo), covered / (hi - lo)
    # the breakdown labels idle stretches with the program's own spans
    labels = {name for name, _ in
              tracefile.from_raw(raw).idle_gaps(len(SPANS) + 1)}
    assert {"repro-executor/launch_scan", "bench-server/collect"} <= labels


def _pairs_of(eng, vecs_seqs, patterns):
    """(computed, needed) from the shapes: one query row per request,
    each predicate's rows once in the descriptor region, and, where the
    wave is floored (a runtime with chains, or more chain runs than
    query rows), the resident tail's floor of one column tile (empty
    here)."""
    _, seqs = vecs_seqs
    rows = {p: sum(p in s for s in seqs) for p in set(patterns)}
    vm = eng.index
    runs = sum(len(vm.runtime.chain_cover(vm.esam.walk(p)).raw_segments)
               for p in set(patterns))
    tail = (MAX_BLOCK_N if ops.floored(len(patterns), runs, 0,
                                       vm.runtime.chains) else 0)
    return (ops.bucket(len(patterns))
            * (ops.bucket(sum(rows.values())) + tail),
            sum(rows[p] for p in patterns))


@pytest.mark.parametrize("quantize", [None, "sq8"])
def test_scan_pairs_equal_the_shape_arithmetic(quantize):
    eng = _engine(n=1500, quantize=quantize)
    patterns = ["ab", "ab", "cd", "a", "bc", "a", "c"]
    q = np.random.default_rng(2).standard_normal(
        (len(patterns), DIM)).astype(np.float32)
    before = eng.maintenance_stats()
    eng.query_batch(q, patterns, 5)
    after = eng.maintenance_stats()
    got = tuple(after[f"traffic_scan_pairs_{x}"]
                - before[f"traffic_scan_pairs_{x}"]
                for x in ("computed", "needed"))
    # a floored shape's first SQ8 batch runs its fp32 program too
    runs = 2 if quantize == "sq8" and eng.index.runtime.chains else 1
    assert got == tuple(runs * x
                        for x in _pairs_of(eng, _corpus(1500), patterns))


def test_an_escalated_sq8_batch_counts_both_launches(monkeypatch):
    from repro.kernels import quant
    eng = _engine(n=1500, quantize="sq8")
    real = quant.topk_sq8_segmented_desc

    def uncertified(*args, **kwargs):
        v, g, cert = real(*args, **kwargs)
        return v, g, cert & False

    monkeypatch.setattr(quant, "topk_sq8_segmented_desc", uncertified)
    patterns = ["ab", "cd", "a"]
    q = np.random.default_rng(3).standard_normal(
        (len(patterns), DIM)).astype(np.float32)
    eng.query_batch(q, patterns, 5)
    st = eng.maintenance_stats()
    assert st["sq8_escalations"] == 1
    computed, needed = _pairs_of(eng, _corpus(1500), patterns)
    assert st["traffic_scan_pairs_computed"] == 2 * computed
    assert st["traffic_scan_pairs_needed"] == 2 * needed


def test_batcher_counters_and_the_merge_timer_parts():
    eng = _engine(n=1500)
    b = ContinuousBatcher(eng, max_wave=3, budget=10 ** 9)
    rng = np.random.default_rng(4)
    try:
        for i in range(7):
            b.submit(_request(rng, i))
        st = eng.maintenance_stats()
        assert st["batcher_submitted"] == 7
        assert st["batcher_submit_lock_ms"] >= 0
        assert st.get("batcher_admitted", 0) == 0
        out = b.drain()
        st = eng.maintenance_stats()
    finally:
        b.close()
    assert len(out) == 7
    assert st["batcher_admitted"] == 7 and st["pipeline_waves"] == 3
    assert st["batcher_queue_wait_ms"] > 0
    # dispatch-side merge launch, device wait and host merge, apart
    for key in ("time_merge_launch_ms", "time_fetch_sync_ms",
                "time_merge_ms"):
        assert st[key] > 0, key


def test_compiles_count_a_new_shape_bucket_and_not_a_repeat():
    eng = _engine(n=1500)
    rng = np.random.default_rng(5)

    def wave(rows):
        q = rng.standard_normal((rows, DIM)).astype(np.float32)
        before = eng.maintenance_stats()
        eng.query_batch(q, ["ab"] * rows, 5)
        after = eng.maintenance_stats()
        return (after["jit_compiles"] - before["jit_compiles"],
                after["jit_compile_ms"] - before["jit_compile_ms"])

    wave(3)                                     # first use of the engine
    assert wave(3) == (0, 0)                    # the same buckets again
    compiles, ms = wave(200)                    # query rows: 128 -> 256
    assert compiles > 0 and ms > 0


def test_a_span_without_a_profiler_raises_nothing_and_keeps_nothing():
    names = set(vars(telemetry))
    before = telemetry.compile_stats()
    with telemetry.span("probe", wave=3) as s:
        s.set_metadata(ticket=4)
    with pytest.raises(KeyError):
        with telemetry.span("probe", wave=3):
            raise KeyError("passes through")
    assert telemetry.compile_stats() == before
    assert set(vars(telemetry)) == names


def test_build_and_residual_counters():
    """The build counters are set once the index is built; the residual
    counters move with a wave that verifies a LIKE."""
    eng = _engine(n=1500)
    st = eng.maintenance_stats()
    assert st["esam_symbols"] == 1500 * 6
    for key in ("time_build_esam_ms", "time_build_states_ms",
                "time_build_runtime_ms"):
        assert st[key] > 0, key
    assert st["residual_rows_verified"] == 0
    assert st["time_residual_verify_ms"] == 0
    q = np.random.default_rng(6).standard_normal((2, DIM)).astype(np.float32)
    eng.query_batch(q, ["LIKE 'a%d'", "ab"], 5)
    st = eng.maintenance_stats()
    assert st["residual_rows_verified"] >= 5
    assert st["time_residual_verify_ms"] > 0
    assert st["traffic_scan_descriptors"] >= 1
