"""Fig. 9 analogue: QPS vs recall for every method × pattern length.

Datasets are the synthetic shape-mirrors of the paper's corpora
(data/corpora.py); the claims validated are the *orderings*: VectorMaton ≈
OptQuery ≫ PostFiltering at long patterns; PreFiltering slow at short
patterns; VectorMaton recall flat in |p| while PostFiltering collapses.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.baselines import (OptQuery, PostFiltering, PreFiltering,
                                  ground_truth, recall)
from repro.core.vectormaton import VectorMaton, VectorMatonConfig
from repro.data.corpora import make_corpus, sample_patterns

from .common import emit, save_json

EF_GRID = [8, 16, 32, 64, 128]
K = 10


def run(corpus: str = "words", scale: float = 0.25, n_queries: int = 100,
        seed: int = 0):
    vecs, seqs = make_corpus(corpus, scale=scale, seed=seed)
    dim = vecs.shape[1]
    rng = np.random.default_rng(seed)

    vm = VectorMaton(vecs, seqs, VectorMatonConfig(T=50, M=8, ef_con=60))
    pre = PreFiltering(vecs, seqs)
    post = PostFiltering(vecs, seqs, M=8, ef_con=60)
    try:
        opt = OptQuery(vecs, seqs, M=8, ef_con=60, T=50, max_pattern_len=4)
    except MemoryError:  # the paper's OOM row
        opt = None

    results = {"corpus": corpus, "n": len(seqs),
               "total_len": sum(len(s) for s in seqs), "curves": {}}
    for plen in (2, 3, 4):
        pats = sample_patterns(seqs, plen, n_queries, seed=seed)
        queries = rng.standard_normal((n_queries, dim)).astype(np.float32)
        gts = [ground_truth(vecs, vm.esam, p, q, K)
               for q, p in zip(queries, pats)]
        for name, idx in [("VectorMaton", vm), ("PreFiltering", pre),
                          ("PostFiltering", post), ("OptQuery", opt)]:
            if idx is None:
                continue
            curve = []
            for ef in EF_GRID:
                t0 = time.perf_counter()
                recs = [recall(idx.query(q, p, K, ef_search=ef)[1], gt)
                        for (q, p), gt in zip(zip(queries, pats), gts)]
                dt = time.perf_counter() - t0
                curve.append({"ef": ef, "qps": n_queries / dt,
                              "recall": float(np.mean(recs))})
                if name == "PreFiltering":
                    break  # no ef dependence
            results["curves"][f"{name}|p{plen}"] = curve
            best = max(curve, key=lambda c: c["recall"])
            emit(f"qps_recall/{corpus}/{name}/p{plen}",
                 1e6 / best["qps"],
                 f"recall={best['recall']:.3f};qps={best['qps']:.0f}")
    save_json(f"qps_recall_{corpus}", results)
    return results


def run_batched(corpus: str = "words", scale: float = 0.25,
                n_requests: int = 96, share: int = 8, seed: int = 0):
    """Cross-request batching: per-request `query` loop vs the coalesced
    `serve_batch` planner/executor path on a workload where `share`
    requests hit each pattern state (the paper's multi-user regime)."""
    from repro.serve.engine import Request, RetrievalEngine

    vecs, seqs = make_corpus(corpus, scale=scale, seed=seed)
    dim = vecs.shape[1]
    rng = np.random.default_rng(seed)
    # Skip-build region: raw CSR segments dominate, so the fused segmented
    # sweep (not per-graph beam searches) carries the batch.
    eng = RetrievalEngine(vecs, seqs, VectorMatonConfig(T=100_000))

    pats = sample_patterns(seqs, 3, max(1, n_requests // share), seed=seed)
    workload = [pats[i % len(pats)] for i in range(n_requests)]
    queries = rng.standard_normal((n_requests, dim)).astype(np.float32)
    reqs = [Request(vector=q, pattern=p, k=K)
            for q, p in zip(queries, workload)]
    plan = eng.index.plan(workload)

    # warm-up both paths, then time
    eng.serve(reqs[0])
    eng.serve_batch(reqs[:4])
    t0 = time.perf_counter()
    per_request = [eng.serve(r) for r in reqs]
    t_seq = time.perf_counter() - t0
    t0 = time.perf_counter()
    batched = eng.serve_batch(reqs)
    t_bat = time.perf_counter() - t0

    for a, b in zip(per_request, batched):   # parity guard
        assert np.array_equal(a.ids, b.ids), "batched != per-request"
    qps_seq = n_requests / t_seq
    qps_bat = n_requests / t_bat
    out = {"corpus": corpus, "n_requests": n_requests,
           "distinct_states": len(plan.entries),
           "coalesced": plan.coalesced,
           "qps_per_request": qps_seq, "qps_batched": qps_bat,
           "speedup": qps_bat / qps_seq}
    emit(f"qps_batched/{corpus}/share{share}", 1e6 / qps_bat,
         f"speedup={out['speedup']:.2f}x;qps_seq={qps_seq:.0f};"
         f"qps_batched={qps_bat:.0f}")
    save_json(f"qps_batched_{corpus}", out)
    return out


def run_device_smoke(profile: bool = False, seed: int = 0) -> dict:
    """Acceptance smoke for the device-resident executor (jax backend,
    DESIGN.md §3): asserts (1) zero candidate-id bytes shipped for
    frozen-base chain/scan sources, (2) one beam launch per graph size
    bucket — not per state — and (3) a bounded executable count across a
    20-shape batch sweep.  ``profile=True`` additionally prints the
    host↔device traffic breakdown the gate reads."""
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    n, dim, k = 300, 16, 8
    seqs = ["".join(rng.choice(list("abcd"), size=rng.integers(5, 15)))
            for _ in range(n)]
    vecs = rng.standard_normal((n, dim)).astype(np.float32)
    preds = ["a", "ab", "abc", "ba", "a OR cd", "cd", "b", "dc"]

    # (1) frozen-base chain/scan sources ship zero candidate-id bytes
    vm_raw = VectorMaton(vecs, seqs,
                         VectorMatonConfig(T=10 ** 9, backend="jax"))
    q = rng.standard_normal((len(preds), dim)).astype(np.float32)
    vm_raw.query_batch(q, preds, k)
    tf = vm_raw.runtime.traffic
    assert tf["candidate_id_bytes"] == 0, tf
    assert tf["row_bytes"] == 0, tf

    # (2) one beam launch per graph bucket, not per state
    vm_g = VectorMaton(vecs, seqs,
                       VectorMatonConfig(T=5, M=8, ef_con=50,
                                         backend="jax"))
    plan = vm_g.plan(preds)
    states = {u for e in plan.entries for s in e.sources
              for u in s.graph_states}
    dev = vm_g.runtime.to_device()
    buckets = {dev["graph_slot"][u][0] for u in states}
    ops.reset_launch_stats()
    vm_g.query_batch(q, preds, k)
    stats = ops.launch_stats()
    assert stats.get("graph_fused", 0) == len(buckets), (stats, buckets)
    assert len(buckets) <= len(states)

    # (3) bounded executables across a 20-shape batch sweep
    ops.reset_launch_stats()
    for size in range(1, 21):
        mix = [preds[(size + j) % len(preds)] for j in range(size)]
        qs = rng.standard_normal((size, dim)).astype(np.float32)
        vm_g.query_batch(qs, mix, k)
    stats = ops.launch_stats()
    assert stats["executables"] <= 24, stats
    assert stats["executables"] <= stats["launches"] // 4, stats
    out = {"graph_states": len(states), "graph_buckets": len(buckets),
           "sweep_launches": stats["launches"],
           "sweep_executables": stats["executables"],
           "traffic": dict(vm_g.runtime.traffic)}
    emit("qps_recall/device_smoke", stats["launches"],
         f"buckets={len(buckets)};executables={stats['executables']};"
         f"frozen_candidate_id_bytes=0")
    if profile:
        batches = max(1, vm_g.runtime.traffic["batches"])
        print("# host<->device traffic breakdown (per batch, padded "
              "buckets as shipped):")
        for key in ("query_bytes", "descriptor_bytes",
                    "candidate_id_bytes", "row_bytes", "mask_bytes",
                    "bytes_to_device"):
            print(f"#   {key:>20}: {vm_g.runtime.traffic[key] / batches:10.1f} B")
        print(f"#   {'launches/batch':>20}: "
              f"{stats['launches'] / 20:10.2f}")
        ms = vm_g.maintenance_stats()
        print("# wave timing breakdown (cumulative ms; launch and "
              "merge_launch are trace+dispatch, fetch_sync the wait on "
              "the device, merge the host merge):")
        for key in ("time_plan_ms", "time_upload_ms", "time_launch_ms",
                    "time_merge_launch_ms", "time_fetch_sync_ms",
                    "time_merge_ms"):
            print(f"#   {key:>20}: {ms.get(key, 0.0):10.2f} ms")
            out[key] = float(ms.get(key, 0.0))
        print("# sq8 scan path (batch-level certificate):")
        for key in ("sq8_batches", "sq8_certified", "sq8_escalations",
                    "sq8_fallbacks"):
            print(f"#   {key:>20}: {ms.get(key, 0):10d}")
            out[key] = int(ms.get(key, 0))
        out["pipeline"] = _profile_pipeline(vecs, seqs, q, preds)
    save_json("qps_recall_device_smoke", out)
    return out


def _profile_pipeline(vecs, seqs, queries, preds) -> dict:
    """Stream a short two-tenant workload through the pipelined batcher
    and print the DESIGN.md §7 serving counters (pipeline depth, device
    idle, planner-queue wait, per-tenant depth/p50/p99)."""
    from repro.serve.batching import ContinuousBatcher
    from repro.serve.engine import Request, RetrievalEngine

    eng = RetrievalEngine(vecs, seqs,
                          VectorMatonConfig(T=10 ** 9, backend="jax"))
    b = ContinuousBatcher(eng, max_wave=len(preds), pipeline=True,
                          tenant_weights={"a": 2.0, "b": 1.0})
    for wave in range(6):
        for j, p in enumerate(preds):
            b.submit(Request(vector=queries[j % len(queries)], pattern=p,
                             k=8, tenant="a" if j % 3 else "b"))
    b.drain()
    st = b.maintenance_stats()
    b.close()
    keys = ("pipeline_waves", "pipeline_depth", "pipeline_replans",
            "pipeline_barriers", "device_idle_ms", "planner_wait_ms",
            "staging_grows", "staging_waits")
    print("# pipelined serving counters (6 waves, 2 tenants, "
          "DESIGN.md §7):")
    for key in keys:
        v = st.get(key, 0)
        print(f"#   {key:>20}: {v:10.2f}" if isinstance(v, float)
              else f"#   {key:>20}: {v:10d}")
    for t, ts in sorted(st.get("tenants", {}).items()):
        print(f"#   tenant[{t}]: depth={ts['depth']} "
              f"served={ts['served']} p50={ts['p50_ms']:.2f}ms "
              f"p99={ts['p99_ms']:.2f}ms")
    return {k: st.get(k, 0) for k in keys} | {
        "tenants": st.get("tenants", {})}


def main():
    for corpus in ("spam", "words"):
        run(corpus)
        run_batched(corpus)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="device-resident executor acceptance checks only")
    ap.add_argument("--profile", action="store_true",
                    help="print the host<->device traffic breakdown used "
                         "by the acceptance gate")
    args = ap.parse_args()
    if args.smoke or args.profile:
        run_device_smoke(profile=args.profile)
    else:
        main()
