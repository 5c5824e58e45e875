"""Scalability: index growth, parallel build, and the real-scale
recall@k/QPS frontier (BENCH_PR6).

(a) Fig. 11 analogue — index size/time vs data fraction (the paper's
near-linear empirical growth despite the O(m^1.5) bound) and parallel
construction speedup vs worker count.

(b) Real-scale frontier — every earlier trajectory (BENCH_PR4/PR5)
measures n≈301, dim=16; this one runs the streamed scale corpus
(``data/corpora.py``: exact hash-decided pattern selectivities from
~0.5 down to ~0.01) at 10^5 vectors and 128+ dims on the XLA-compiled
kernels (``ops.default_impl() == "xla"`` off-TPU — NOT Pallas
interpret mode), and records recall@k + QPS against the brute-force
oracle for each serving strategy:

  * ``scan``        — fp32 segmented scan, legacy candidate-id upload
                      (``use_descriptors=False``);
  * ``chain_desc``  — fp32 descriptor-resolved scan against the
                      device-resident CSR (the PR 4 hot path);
  * ``sq8_rerank``  — raw int8 scan + fp32 rerank tail with the
                      certificate sync skipped (``sq8_escalate=False``)
                      — the approximate operating point; recall is
                      whatever the over-fetch actually delivers;
  * ``sharded``     — the 8-shard sweep (DESIGN.md §5; quantized with
                      per-shard certificates when eligible).

The sq8 DEFAULT (certificate + adaptive escalation) is additionally
asserted to match the fp32 scan's ids exactly — the exactness contract
the certificate guarantees at any scale.

Writes ``BENCH_PR6.json`` with a ``smoke`` section (what
``scripts/ci.sh`` regenerates and gates: recall@10 must not drop, QPS
must stay within tolerance) and a ``full`` section (the committed
≥100k-vector frontier; refreshed only by a full run).

    PYTHONPATH=src python -m benchmarks.bench_scalability --smoke \
        --baseline BENCH_PR6.json
"""

from __future__ import annotations

import os

# must land before jax initializes: the sharded strategy needs a mesh
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import argparse
import json
import sys
import time
from typing import Dict, List

import numpy as np

from repro.core.vectormaton import VectorMaton, VectorMatonConfig
from repro.data.corpora import (SCALE_PATTERNS, make_corpus,
                                make_scale_corpus)
from repro.kernels import ops

from .common import emit, save_json

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")
TRAJECTORY = os.path.join(REPO_ROOT, "BENCH_PR6.json")

K = 10
QPS_TOLERANCE = 0.35        # gated QPS may sink to this fraction of base
FULL_POINTS = [(32768, 128), (32768, 256), (131072, 128)]
SMOKE_POINTS = [(8192, 128)]


# --------------------------------------------------------------------- #
# (a) Fig. 11: index growth + parallel build
# --------------------------------------------------------------------- #

def run_growth(corpus: str = "words", scale: float = 0.5):
    vecs, seqs = make_corpus(corpus, scale=scale)
    fractions = [0.2, 0.4, 0.6, 0.8, 1.0]
    rows = []
    for f in fractions:
        n = max(4, int(len(seqs) * f))
        t0 = time.perf_counter()
        vm = VectorMaton(vecs[:n], seqs[:n],
                         VectorMatonConfig(T=50, M=8, ef_con=60))
        dt = time.perf_counter() - t0
        m = sum(len(s) for s in seqs[:n])
        rows.append({"fraction": f, "m": m,
                     "size_entries": vm.size_entries(),
                     "id_entries": vm.esam.total_id_entries(),
                     "states": vm.esam.num_states,
                     "build_s": dt})
        emit(f"scalability/{corpus}/f{f}", dt * 1e6,
             f"m={m};entries={rows[-1]['size_entries']}")
    # near-linearity check: growth exponent of size vs m (paper: ~1)
    ms = np.log([r["m"] for r in rows])
    sz = np.log([r["id_entries"] for r in rows])
    slope = float(np.polyfit(ms, sz, 1)[0])
    emit(f"scalability/{corpus}/growth_exponent", 0.0, f"slope={slope:.3f}")
    return {"rows": rows, "growth_exponent": slope}


def run_parallel(corpus: str = "mtg", scale: float = 0.08):
    vecs, seqs = make_corpus(corpus, scale=scale)
    rows = []
    base = None
    for workers in (1, 2, 4):
        t0 = time.perf_counter()
        VectorMaton(vecs, seqs, VectorMatonConfig(T=30, M=8, ef_con=60),
                    workers=workers)
        dt = time.perf_counter() - t0
        base = base or dt
        rows.append({"workers": workers, "build_s": dt,
                     "speedup": base / dt})
        emit(f"parallel_build/{corpus}/w{workers}", dt * 1e6,
             f"speedup={base/dt:.2f}x")
    return rows


# --------------------------------------------------------------------- #
# (b) real-scale recall/QPS frontier
# --------------------------------------------------------------------- #

def _oracle_topk(vecs: np.ndarray, seqs: List[str], queries: np.ndarray,
                 preds: List[str], k: int) -> List[np.ndarray]:
    """Exact brute-force ids per (query, pattern), grouped by pattern so
    each qualified set is scanned once for all its queries."""
    out: List[np.ndarray] = [None] * len(preds)  # type: ignore
    by_pat: Dict[str, List[int]] = {}
    for i, p in enumerate(preds):
        by_pat.setdefault(p, []).append(i)
    for p, rows in by_pat.items():
        qual = np.fromiter((p in s for s in seqs), bool, count=len(seqs))
        ids = np.nonzero(qual)[0]
        sub = vecs[ids]
        x = queries[rows]
        d = ((x * x).sum(1, keepdims=True) + (sub * sub).sum(1)
             - 2.0 * (x @ sub.T))
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        for j, i in enumerate(rows):
            out[i] = ids[order[j]]
    return out


def _recall(res, oracle, k: int) -> float:
    return float(np.mean([
        len(set(ids[:k].tolist()) & set(o[:k].tolist())) / k
        for (_, ids), o in zip(res, oracle)]))


def run_point(n: int, dim: int, n_queries: int = 64, waves: int = 4,
              k: int = K, seed: int = 0) -> dict:
    """Frontier measurements for one (n, dim) corpus point."""
    from repro.distributed.sharded_search import sharded_plan_topk
    from repro.launch.mesh import make_host_mesh

    vecs, seqs = make_scale_corpus(n, dim, seed=seed)
    vm = VectorMaton(vecs, seqs,
                     VectorMatonConfig(T=10 ** 9, backend="jax"))
    rt = vm.runtime
    preds = [SCALE_PATTERNS[j % len(SCALE_PATTERNS)]
             for j in range(n_queries)]
    rng = np.random.default_rng(seed + 1)
    q_eval = rng.standard_normal((n_queries, dim)).astype(np.float32)
    oracle = _oracle_topk(vecs, seqs, q_eval, preds, k)

    def measure(label, answer):
        res_eval = answer(q_eval)               # warm-up + recall wave
        lat: List[float] = []
        for _ in range(waves):
            qw = rng.standard_normal((n_queries, dim)).astype(np.float32)
            t0 = time.perf_counter()
            answer(qw)
            lat.append(time.perf_counter() - t0)
        lat_ms = np.asarray(lat) * 1e3
        rec = _recall(res_eval, oracle, k)
        row = {"strategy": label, "n": n, "dim": dim,
               "recall_at_k": rec,
               "qps": n_queries * waves / float(np.sum(lat)),
               "p50_ms": float(np.percentile(lat_ms, 50))}
        emit(f"scalability/{label}/n{n}/d{dim}", 1e6 / row["qps"],
             f"recall={rec:.4f};qps={row['qps']:.0f}")
        return row, res_eval

    def vm_answer(qw):
        return vm.query_batch(qw, preds, k)

    rows = []
    # fp32 scan, legacy id upload
    rt.quantize = "none"
    rt.use_descriptors = False
    row, res_scan = measure("scan", vm_answer)
    rows.append(row)
    # fp32 descriptor scan
    rt.use_descriptors = True
    row, res_desc = measure("chain_desc", vm_answer)
    rows.append(row)
    # raw sq8 + rerank tail (no certificate sync — the approximate point)
    rt.quantize = "sq8"
    rt.sq8_escalate = False
    row, _ = measure("sq8_rerank", vm_answer)
    rows.append(row)
    # sq8 DEFAULT exactness: certificate + escalation must reproduce the
    # fp32 scan ids bit-for-bit at any scale (the adaptive fallback may
    # kick in after SQ8_MAX_STREAK failed batches — still exact)
    rt.sq8_escalate = True
    rt._sq8_bad_streak.clear()
    res_dflt = vm_answer(q_eval)
    sq8_exact = all(np.array_equal(a[1], b[1])
                    for a, b in zip(res_dflt, res_desc))
    # sharded sweep over the 8-shard host mesh
    mesh = make_host_mesh(data=8, model=1)
    rt._sq8_bad_streak.clear()

    def sharded_answer(qw):
        snap = vm.snapshot()
        plan = vm.plan(preds, snap)
        return sharded_plan_topk(mesh, None, snap, qw, plan, k)

    row, _ = measure("sharded", sharded_answer)
    rows.append(row)

    # exact strategies must reproduce the oracle
    for label, res in (("scan", res_scan), ("chain_desc", res_desc)):
        rec = _recall(res, oracle, k)
        assert rec == 1.0, f"{label} recall {rec} != 1.0 vs oracle"
    return {"n": n, "dim": dim, "rows": rows,
            "sq8_default_exact": sq8_exact,
            "sq8_stats": dict(rt.sq8_stats)}


def run_frontier(points, n_queries: int = 64, waves: int = 4,
                 seed: int = 0) -> dict:
    impl = ops.default_impl()
    out = {
        "config": {"points": [list(p) for p in points], "k": K,
                   "n_queries": n_queries, "waves": waves, "impl": impl,
                   "shards": 8},
        "frontier": [],
        "sq8_default_exact": True,
    }
    for n, dim in points:
        pt = run_point(n, dim, n_queries=n_queries, waves=waves,
                       seed=seed)
        out["frontier"].extend(pt["rows"])
        out["sq8_default_exact"] &= pt["sq8_default_exact"]
        out["sq8_stats"] = pt["sq8_stats"]
    return out


def check_baseline(out: dict, base: dict | None) -> List[str]:
    """Recall floor + QPS tolerance against the committed trajectory."""
    errs: List[str] = []
    if out["config"]["impl"] == "pallas":
        errs.append("frontier ran on the Pallas interpret path, not the "
                    "compiled kernels (REPRO_IMPL?)")
    if not out["sq8_default_exact"]:
        errs.append("sq8 default path diverged from the fp32 scan ids")
    for row in out["frontier"]:
        if row["strategy"] in ("scan", "chain_desc") \
                and row["recall_at_k"] != 1.0:
            errs.append(f"{row['strategy']} n={row['n']} is not exact: "
                        f"recall {row['recall_at_k']}")
    if base is None:
        return errs
    if base.get("config") != out.get("config"):
        print("# baseline config differs; scalability gate skipped",
              file=sys.stderr)
        return errs
    by_key = {(r["strategy"], r["n"], r["dim"]): r
              for r in base.get("frontier", [])}
    for row in out["frontier"]:
        b = by_key.get((row["strategy"], row["n"], row["dim"]))
        if b is None:
            continue
        if row["recall_at_k"] < b["recall_at_k"] - 1e-9:
            errs.append(
                f"recall@{K} regressed for {row['strategy']} "
                f"n={row['n']} d={row['dim']}: "
                f"{b['recall_at_k']:.4f} -> {row['recall_at_k']:.4f}")
        if row["qps"] < QPS_TOLERANCE * b["qps"]:
            errs.append(
                f"QPS collapsed for {row['strategy']} n={row['n']} "
                f"d={row['dim']}: {b['qps']:.0f} -> {row['qps']:.0f} "
                f"(tolerance {QPS_TOLERANCE:.0%})")
    return errs


def main() -> dict:
    """Harness entry point (``benchmarks.run``): the quick Fig. 11
    growth + parallel-build study.  The gated frontier runs from the
    CLI (``--smoke`` in ci.sh, no flags for the full committed run)."""
    out = {"growth": run_growth(), "parallel": run_parallel()}
    save_json("scalability", out)
    return out


def frontier_main(smoke: bool = False,
                  baseline: str | None = None) -> dict:
    mode = "smoke" if smoke else "full"
    if smoke:
        out = run_frontier(SMOKE_POINTS, n_queries=32, waves=3)
    else:
        out = run_frontier(FULL_POINTS)
    base_doc = {}
    if baseline and os.path.exists(baseline):
        with open(baseline) as f:
            base_doc = json.load(f)
    errs = check_baseline(out, base_doc.get(mode))
    save_json(f"scalability_{mode}", out)
    if errs:
        # keep the committed trajectory intact so the gate keeps firing
        for e in errs:
            print(f"# SCALABILITY GATE FAILED: {e}", file=sys.stderr)
        sys.exit(1)
    doc = {}
    if os.path.exists(TRAJECTORY):
        with open(TRAJECTORY) as f:
            doc = json.load(f)
    doc[mode] = out
    with open(TRAJECTORY, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="scaled-down frontier (the CI gate config)")
    ap.add_argument("--baseline", default=None,
                    help="previous BENCH_PR6.json to gate recall/QPS "
                         "against")
    ap.add_argument("--growth", action="store_true",
                    help="legacy Fig. 11 index-growth + parallel-build "
                         "run instead of the frontier")
    args = ap.parse_args()
    if args.growth:
        main()
    else:
        frontier_main(smoke=args.smoke, baseline=args.baseline)
