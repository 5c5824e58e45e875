"""The benchmark's yardstick on the CPU: the peaks table, the scan's work
function, the copies of the data generator and of the reference, the
traffic generator, the metric readers, and the reduction of a profiler
trace recorded on one TPU v5e: a 0.4 s window of descriptor scans over
1M x 128 rows at 100 req/s, kept as ``tracefile.read``'s extraction in
``data/sift_window.trace.json.gz``.
"""

from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import corpus, predicates, reference, tracefile, traffic  # noqa: E402
from bench import run as bench_run  # noqa: E402

TRACE = Path(__file__).resolve().parent / "data" / "sift_window.trace.json.gz"
SCAN = bench_run.load_module("kernels", "scan")


def test_peaks_of_v5e_and_unknown_kind():
    p = bench_run.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        bench_run.load_peaks("TPU v9 imaginary")


def test_scan_work_on_hand_computed_shapes():
    sizes = {"a": 100, "b": 50, "x": 7}
    scanned = {"a": True, "b": True, "x": False}
    flops, nbytes = SCAN.work([["a", "b", "x"], ["a", "a"]], sizes,
                              scanned, dim=4, k=2)
    # FLOPs 2 d |S_p| per request: (100 + 50) + (100 + 100) rows
    assert flops == 2 * 4 * 350
    # each distinct predicate's rows once per wave, 4 B per element,
    # plus a query (d x 4 B) and k (distance, id) pairs per request
    assert nbytes == (150 * 16 + 2 * (16 + 16)) + (100 * 16 + 2 * (16 + 16))
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert SCAN.least_time(flops, nbytes, peaks) == (nbytes / 1e3,
                                                     "bandwidth")
    assert SCAN.least_time(5e3, 1e3, peaks) == (5.0, "FLOPs")


def test_corpus_copy_matches_the_program_generator():
    from repro.data.corpora import make_scale_corpus
    n, d, seed = 9000, 24, 2 ** 31 + 17
    vecs, _ = make_scale_corpus(n, d, seed)
    assert np.array_equal(corpus.vectors(n, d, seed, False), vecs)
    unit = corpus.vectors(n, d, seed, True)
    assert np.allclose(np.linalg.norm(unit, axis=1), 1.0, atol=1e-6)
    # one label per row, uniform over the 12, the same for the same seed
    codes = corpus.labels(n, seed, 12)
    assert np.array_equal(codes, corpus.labels(n, seed, 12))
    assert not np.array_equal(codes, corpus.labels(n, seed + 1, 12))
    share = np.bincount(codes, minlength=12) / n
    assert np.all(np.abs(share - 1 / 12) < 4 * np.sqrt(1 / 12 / n))
    vocab = [chr(ord("a") + i) for i in range(12)]
    assert corpus.sequences(codes[:5], vocab) == [vocab[c] for c in codes[:5]]


def test_predicate_evaluator_agrees_with_the_program_parser():
    from repro.core.predicate import parse_predicate
    mixes = [traffic.load(p.stem) for p in
             (ROOT / "bench" / "traffic").glob("*.json")]
    texts = {p for mix in mixes for p in mix["predicates"]}
    texts |= {"NOT (a OR b)", "CONTAINS 'cz' OR LIKE 'a_c%'", "a AND b",
              "c AND NOT a", "d OR e", "LIKE '%b%d%'", "NOT LIKE '%a%'",
              "c AND NOT LIKE '%a%d%'"}
    # each configuration's own sequences: its labels where it has them,
    # and the rows its corpus makes
    cfgs = [json.loads(c.read_text())
            for c in (ROOT / "bench" / "configs").glob("*.json")]
    strings = sorted({s for cfg in cfgs
                      for s in [*cfg.get("labels", ()),
                                *corpus.rows(cfg, 64, 1)[1]]}
                     | {"z", "az", "abz", "acz", "bcz", "bdz", "cez", "abcdez"})
    got = predicates.members(texts, strings)
    for text in texts:
        want = [r for r, s in enumerate(strings)
                if parse_predicate(text).matches(s)]
        assert got[text].tolist() == want, text


@pytest.mark.parametrize("count", [0, 257, 1000])
def test_labels_refuse_a_count_a_byte_cannot_hold(count):
    with pytest.raises(ValueError):
        corpus.labels(10, 1, count)
    assert corpus.labels(5000, 1, 256).max() == 255


def test_schedule_is_the_same_work_in_another_order():
    cfg = {"dim": 8, "normalize": False}
    mix = {"rate_per_s": 50, "predicates": ["a", "b", "c"]}
    one = traffic.schedule(mix, cfg, 1, 4.0)
    two = traffic.schedule(mix, cfg, 2 ** 31 + 5, 4.0)
    assert len(one) == len(two) == 200
    for s in (one, two):
        off = np.asarray([o for o, _, _ in s])
        assert off[0] == 0 and np.all(np.diff(off) > 0) and off[-1] < 4.0
    assert sorted(p for _, _, p in one) == sorted(p for _, _, p in two)
    # every run of three requests holds each predicate once
    assert all(sorted(p for _, _, p in one[i:i + 3]) == ["a", "b", "c"]
               for i in range(0, 198, 3))
    # the gaps are drawn, without repeats, from one fixed set
    u = (np.arange(200) + 0.5) / 200
    base = -np.log1p(-u)
    base *= 4.0 / base.sum()
    for s in (one, two):
        gaps = np.diff([o for o, _, _ in s])
        assert np.abs(gaps[:, None] - base[None, :]).min(1).max() < 1e-9
    assert [p for _, _, p in one] != [p for _, _, p in two]


@pytest.fixture
def toy():
    rng = np.random.default_rng(0)
    n = 3000
    vocab = ["a", "b", "c", "bc"]
    seqs = corpus.sequences(corpus.labels(n, 0, len(vocab)), vocab)
    vecs = rng.standard_normal((n, 16)).astype(np.float32)
    members = predicates.members(("a", "b AND NOT c"), seqs)
    queries = rng.standard_normal((6, 16)).astype(np.float32)
    pats = ["a", "b AND NOT c"] * 3
    return reference.Reference(vecs, "l2", members), queries, pats


def _exact(ref, queries, pats, k):
    out = []
    for q, p in zip(queries, pats):
        ids = ref.members(p)
        d = ((ref.vecs[ids].astype(np.float64) - q) ** 2).sum(1)
        o = np.lexsort((ids, d))[:k]
        out.append((d[o].astype(np.float32), ids[o]))
    return out


def test_reference_accepts_exact_answers_and_refuses_wrong_ones(toy):
    ref, queries, pats = toy
    good = _exact(ref, queries, pats, 10)
    ok = reference.compare(ref, queries, pats, good, 10, 1e-5)
    assert ok["wrong_answers"] == 0 and ok["dist_err"] < 1e-6
    bad = [(d, i[::-1].copy()) for d, i in good[:3]] + good[3:]
    assert reference.compare(ref, queries, pats, bad, 10,
                             1e-5)["wrong_answers"] == 3
    short = [(d[:5], i[:5]) for d, i in good]
    assert reference.compare(ref, queries, pats, short, 10,
                             1e-5)["wrong_answers"] == 6
    off = [(d + 0.01, i) for d, i in good]
    assert reference.compare(ref, queries, pats, off, 10,
                             1e-5)["dist_err"] > 1e-5


def test_reference_accepts_a_near_tie(toy):
    ref, queries, pats = toy
    good = _exact(ref, queries, pats, 10)
    d, ids = good[0]
    twin = ids[4]
    ref.vecs = ref.vecs.copy()
    ref.vecs[ids[3]] = ref.vecs[twin]        # rank 3 and 4 now tie exactly
    ref.y2 = np.einsum("nd,nd->n", ref.vecs, ref.vecs, dtype=np.float64)
    d, ids = _exact(ref, queries[:1], pats[:1], 10)[0]
    swapped = ids.copy()
    swapped[[3, 4]] = swapped[[4, 3]]
    res = reference.compare(ref, queries[:1], pats[:1], [(d, swapped)], 10,
                            1e-5)
    assert res["wrong_answers"] == 0


def test_control_high_rounds_below_float32(toy):
    ref, queries, pats = toy
    got = reference.control_high(ref, queries, pats, 10)
    res = reference.compare(ref, queries, pats, got, 10, 1.0)
    exact = reference.compare(ref, queries, pats,
                              _exact(ref, queries, pats, 10), 10, 1.0)
    assert res["dist_err"] > 3 * exact["dist_err"]


# --------------------------------------------------------------------- #
# trace reduction

def _synthetic():
    return tracefile.from_raw({
        "window_ns": [0, 50],
        "devices": [{"ops": [["fusion.1", 0, 10], ["scan", 5, 20],
                             ["copy", 30, 40]],
                     "modules": [["jit_distance_topk_descriptors", 0, 20],
                                 ["jit_merge_topk_device", 30, 40]]}],
        "spans": [["repro-executor/dispatch_batch", 18, 26],
                  ["repro-planner/plan_batch", 24, 28]]})


def test_short_names_of_trace_events():
    assert tracefile.op_name("%fusion.2 = f32[8]{0} fusion(f32[8] %x)") \
        == "fusion.2"
    assert tracefile.module_name("jit_merge_topk_device(123456)") \
        == "jit_merge_topk_device"


def test_reduction_of_a_synthetic_trace():
    tr = _synthetic()
    assert tr.busy_s() == 30e-9 and tr.window_s == 50e-9
    assert tr.gaps() == [(20, 30), (40, 50)]
    assert tr.module_s({"jit_distance_topk_descriptors"}) == 20e-9
    assert tr.top_ops(2) == [["jit_distance_topk_descriptors:scan", 15e-9],
                             ["jit_distance_topk_descriptors:fusion.1",
                              10e-9]]
    assert dict(map(tuple, tr.idle_gaps(5))) == {
        "no pipeline stage": 12e-9, "repro-executor/dispatch_batch": 4e-9,
        "repro-planner/plan_batch": 4e-9}


def _recorded():
    with gzip.open(TRACE, "rt") as f:
        return tracefile.from_raw(json.load(f))


def test_reduction_of_a_recorded_chip_trace():
    tr = _recorded()
    assert 0.3 < tr.window_s < 2.0
    assert 0 < tr.busy_s() < tr.window_s
    scan = tr.module_s(SCAN.MODULES)
    assert 0 < scan <= tr.busy_s() + 1e-9
    counts = tr.module_counts()
    assert counts.get("jit_distance_topk_descriptors", 0) > 0
    assert counts.get("jit_merge_topk_device", 0) > 0
    top = tr.top_ops(10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    assert top[0][0].startswith("jit_distance_topk_descriptors:")
    idle = sum(s for _, s in tr.idle_gaps(100))
    assert abs(idle + tr.busy_s() - tr.window_s) < 1e-6
    labels = {name for name, _ in tr.idle_gaps(10)}
    assert labels & {"repro-executor/dispatch_batch",
                     "repro-executor/fetch_batch"}


def test_metric_readers_on_the_recorded_trace():
    tr = _recorded()
    waves = 20
    # the counters, read after the last answer, also hold 3 waves
    # dispatched after the trace stopped; the scan's time is per traced wave
    run = bench_run.RunRecord(
        trace=tr, peaks=bench_run.load_peaks("TPU v5 lite"),
        counters0={"pipeline_waves": 0},
        counters1={"pipeline_waves": waves + 3},
        waves=[["a"]] * waves, sizes={"a": 500_000}, scanned={"a": True},
        cfg={"dim": 128, "k": 10})
    idle = bench_run.load_module("metrics", "device_idle_share").read(run)
    assert idle == pytest.approx(100 * (1 - tr.busy_s() / tr.window_s))
    scan_ms = bench_run.load_module("metrics", "scan_ms_per_wave").read(run)
    assert scan_ms == pytest.approx(tr.module_s(SCAN.MODULES) * 1e3 / waves)
    roof = bench_run.load_module("metrics", "scan_roofline").read(run)
    nbytes = waves * (500_000 * 128 * 4 + 128 * 4 + 10 * 8)
    assert roof == pytest.approx(
        100 * nbytes / 819e9 / tr.module_s(SCAN.MODULES))
    run.trace = tracefile.Trace(window_ns=(0, 10))
    assert bench_run.load_module("metrics", "scan_roofline").read(run) is None


def test_benchmark_json_names_a_file_for_every_entry():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg["reduced"]) and cfg["check"]
        src = corpus.source(cfg)
        assert callable(src.rows) and callable(src.queries)
    for w in spec["workloads"]:
        traffic.load(w["traffic"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bench_run.load_module("metrics", m["name"]).read)
