"""Smoke test of pattern-constrained vector search on the TPU, driven
through the serving entry points (``RetrievalEngine`` and the pipelined
``ContinuousBatcher``) with the device executor (``backend="jax"``) and
the compiled Pallas kernels.

    python chip_smoke.py              # one TPU chip: phases A and B
    python chip_smoke.py --chips 4    # four TPU chips: phase C only
    python chip_smoke.py --rehearse   # the same phases, tiny, on the CPU
                                      # (Pallas kernels in interpret mode)

Phase A, the scan path at the SIFT1M shape: the streamed scale corpus
(1,048,576 × 128, seed 0) indexed with T=10**9, so every automaton state
is a raw segment.  Waves of 64 requests (k=10) mix the 8 SCALE_PATTERNS,
an AND NOT and a LIKE predicate: 4 waves through ``serve_batch``, a write
wave of 1,024 inserts and 1,024 deletes, one more ``serve_batch`` wave
over the live delta, ``compact()``, then 4 waves through the batcher.

Phase B, the graph path: ``make_corpus("mtg")`` with a T low enough that
states hold HNSW graphs (fused beam, filtered beam, residual LIKE, device
merge).  Requests answered by graphs report recall@10, held to the CPU
rehearsal's value less 0.02.

Phase C (``--chips 4`` only): phase A's data, waves and writes through
``RetrievalEngine(mesh=...)`` on a 4-device ``data`` mesh.

Every exact answer is checked against a brute-force NumPy reference over
the live set, independent of the index: id for id, or, where the
reference itself has two distances within float32 rounding of each
other, any order of those ids.  All phases run in this one process, since
a chip belongs to one process.  The exit code is non-zero when no TPU is
found (without ``--rehearse``) or any phase fails; on success the last
line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
K = 10
WAVE = 64
N_WRITES = 1024
# phase B recall@10 of the graph-answered requests, measured by
# ``--rehearse`` on the CPU (XLA CPU backend, Pallas interpret mode)
PHASE_B_CPU_RECALL = 1.0
RECALL_SLACK = 0.02


def log(msg: str) -> None:
    print(msg, flush=True)


class PhaseError(RuntimeError):
    pass


class CompileClock:
    """Seconds JAX spends lowering and compiling programs, summed from its
    monitoring events, so wall times can be reported with compile time
    kept apart.  (Tracing is left out: nested jits report it nested.)"""
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax) -> None:
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.total += duration

    def run(self, label: str, fn):
        c0, t0 = self.total, time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        comp = self.total - c0
        log(f"  time {label}: wall {wall:.3f} s = compile {comp:.3f} s "
            f"+ other {wall - comp:.3f} s")
        return out


# --------------------------------------------------------------------- #
# brute-force reference
# --------------------------------------------------------------------- #

class Reference:
    """Plain NumPy top-k over the live set: the system's own index plays
    no part.  ``members(pattern)`` gives a (rows,) bool mask."""

    def __init__(self, vecs: np.ndarray, members) -> None:
        self.vecs = vecs
        self.y2 = np.einsum("nd,nd->n", vecs, vecs, dtype=np.float64)
        self.alive = np.ones(len(vecs), bool)
        self.members = members

    def append(self, rows: np.ndarray) -> None:
        self.vecs = np.concatenate([self.vecs, rows])
        self.y2 = np.concatenate(
            [self.y2, np.einsum("nd,nd->n", rows, rows, dtype=np.float64)])
        self.alive = np.concatenate([self.alive, np.ones(len(rows), bool)])

    def topk(self, queries, patterns, k: int):
        """Per request: (ids, float64 distances, live member mask)."""
        xy = self.vecs @ queries.T                      # f32 prefilter
        out = []
        for r, p in enumerate(patterns):
            mask = self.members(p) & self.alive
            cand = np.flatnonzero(mask)
            if len(cand) > k + 32:
                approx = self.y2[cand] - 2.0 * xy[cand, r]
                cand = cand[np.argpartition(approx, k + 32)[:k + 32]]
            diff = self.vecs[cand].astype(np.float64) - queries[r]
            dist = np.einsum("nd,nd->n", diff, diff)
            order = np.lexsort((cand, dist))[:k]
            out.append((cand[order], dist[order], mask))
        return out

    def exact_dist(self, q, ids) -> np.ndarray:
        diff = self.vecs[ids].astype(np.float64) - q
        return np.einsum("nd,nd->n", diff, diff)


def check_exact(ref: Reference, queries, patterns, answers, k: int,
                label: str) -> None:
    """Every answer equals the reference id for id, or differs only by
    ids whose true distances lie within float32 rounding of the
    reference's at the same rank.  Raises on anything else."""
    same = near = 0
    wrong = []
    for r, ((want, wd, mask), (got, gd)) in enumerate(
            zip(ref.topk(queries, patterns, k), answers)):
        got = np.asarray(got, np.int64)
        gd = np.asarray(gd, np.float64)
        x2 = float(queries[r].astype(np.float64) @ queries[r])
        tol = 1e-5 * (x2 + (ref.y2[want].max() if len(want) else 0.0))
        if len(got) != len(want):
            wrong.append((r, patterns[r], got, want))
            continue
        ok_d = np.all(np.abs(gd - wd) <= tol)
        if got.tolist() == want.tolist() and ok_d:
            same += 1
            continue
        live = (np.all((got >= 0) & (got < len(mask)))
                and mask[np.clip(got, 0, len(mask) - 1)].all()
                and len(set(got.tolist())) == len(got))
        if live and ok_d and np.all(
                np.abs(ref.exact_dist(queries[r], got) - wd) <= tol):
            near += 1
        else:
            wrong.append((r, patterns[r], got, want))
    log(f"  check {label}: {len(answers)} answers, {same} equal id for "
        f"id, {near} equal up to float32 near-ties, {len(wrong)} wrong")
    if wrong:
        for r, p, got, want in wrong[:4]:
            log(f"    request {r} {p!r}: got {got.tolist()} "
                f"want {want.tolist()}")
        raise PhaseError(f"{label}: {len(wrong)} answers differ from the "
                         "brute-force reference")


def recall_at_k(ref: Reference, queries, patterns, answers, k: int):
    recs = []
    for (want, _, _), (got, _) in zip(ref.topk(queries, patterns, k),
                                      answers):
        if len(want):
            recs.append(len(set(np.asarray(got).tolist())
                            & set(want.tolist())) / len(want))
    return float(np.mean(recs)) if recs else float("nan")


# --------------------------------------------------------------------- #
# shared phase plumbing
# --------------------------------------------------------------------- #

def serve_waves(clock, engine, label, waves):
    """``serve_batch`` each wave of (queries, patterns); returns answers."""
    from repro.serve.engine import Request
    out = []
    for w, (qs, pats) in enumerate(waves):
        reqs = [Request(vector=q, pattern=p, k=K) for q, p in zip(qs, pats)]
        resps = clock.run(f"{label} serve_batch wave {w}",
                          lambda: engine.serve_batch(reqs))
        out.append([(r.ids, r.distances) for r in resps])
    return out


def batcher_waves(clock, engine, label, waves):
    """The same waves through the pipelined ContinuousBatcher: all
    requests submitted, then drained in waves of 64."""
    from repro.serve.batching import ContinuousBatcher
    from repro.serve.engine import Request
    batcher = ContinuousBatcher(engine, budget=1 << 40, max_wave=WAVE,
                                pipeline=True)
    try:
        tickets = [[batcher.submit(Request(vector=q, pattern=p, k=K))
                    for q, p in zip(qs, pats)] for qs, pats in waves]
        got = clock.run(f"{label} ContinuousBatcher drain of "
                        f"{len(waves)} waves", batcher.drain)
    finally:
        batcher.close()
    return [[(got[t].ids, got[t].distances) for t in ts] for ts in tickets]


def report_launches(label: str, want_pallas: bool) -> None:
    from repro.kernels import ops
    stats = ops.launch_stats()
    keys = ops.launch_keys()
    log(f"  launch_stats {label}: {json.dumps(stats, sort_keys=True)}")
    for kind, ks in keys.items():
        log(f"  launch keys {kind}: {ks}")
    scans = [k for kind in ("desc_scan", "sq8_scan") for k in
             keys.get(kind, [])]
    if want_pallas:
        if not scans:
            raise PhaseError(f"{label}: no scan kernel launched")
        bad = [k for k in scans if k[-1] != "pallas"]
        if bad:
            raise PhaseError(f"{label}: scan launches off the Pallas "
                             f"kernels: {bad}")


def report_engine(label: str, engine, dev) -> None:
    st = engine.maintenance_stats()
    sq8 = {k: v for k, v in st.items() if k.startswith("sq8_")}
    log(f"  sq8_stats {label}: {json.dumps(sq8, sort_keys=True)}")
    mem = dev.memory_stats()
    peak = (mem or {}).get("peak_bytes_in_use")
    log(f"  memory {label}: peak_bytes_in_use "
        f"{peak if peak is not None else 'not reported'}")


# --------------------------------------------------------------------- #
# phase A / C: the scale corpus
# --------------------------------------------------------------------- #

def scale_predicates():
    from repro.data.corpora import SCALE_PATTERNS
    preds = {p: (lambda s, p=p: p in s) for p in SCALE_PATTERNS}
    preds["a AND NOT b"] = lambda s: "a" in s and "b" not in s
    preds["LIKE '%b%d%'"] = lambda s: re.fullmatch(".*b.*d.*", s) is not None
    return preds


def tag_codes(ids: np.ndarray) -> np.ndarray:
    """Per id, the 5-bit set of scale tags it carries."""
    from repro.data.corpora import SCALE_TAGS, scale_tag_member
    code = np.zeros(len(ids), np.int64)
    for j, (_, sel) in enumerate(SCALE_TAGS):
        code |= scale_tag_member(ids, j, sel).astype(np.int64) << j
    return code


def code_string(code: int) -> str:
    from repro.data.corpora import SCALE_TAGS
    return "".join(t for j, (t, _) in enumerate(SCALE_TAGS)
                   if code >> j & 1) + "z"


def scale_phase(clock, dev, label: str, n: int, mesh=None) -> None:
    from repro.core.vectormaton import VectorMatonConfig
    from repro.data.corpora import make_scale_corpus
    from repro.kernels import ops
    from repro.serve.engine import RetrievalEngine

    d = 128
    preds = scale_predicates()
    names = list(preds)
    log(f"[{label}] scale corpus {n} x {d}, seed 0, T=10**9, "
        f"{len(names)} predicates, waves of {WAVE}, k={K}"
        + (f", mesh {dict(mesh.shape)}" if mesh is not None else ""))
    ops.reset_launch_stats()
    vecs, seqs = clock.run("corpus", lambda: make_scale_corpus(n, d, 0))
    engine = clock.run("index build", lambda: RetrievalEngine(
        vecs, seqs, VectorMatonConfig(T=10 ** 9, backend="jax"), mesh=mesh))
    codes = tag_codes(np.arange(n, dtype=np.int64))
    table = {p: np.asarray([f(code_string(c)) for c in range(32)])
             for p, f in preds.items()}
    ref = Reference(vecs, lambda p: table[p][codes])
    rng = np.random.default_rng(1)

    def make_waves(count, first):
        return [(ref.vecs[rng.integers(0, n, WAVE)]
                 + 0.5 * rng.standard_normal((WAVE, d)).astype(np.float32),
                 [names[(j + first + w) % len(names)]
                  for j in range(WAVE)]) for w in range(count)]

    def check(stage, waves, answers):
        for w, ((qs, pats), ans) in enumerate(zip(waves, answers)):
            check_exact(ref, qs, pats, ans, K, f"{label} {stage} wave {w}")

    waves = make_waves(4, 0)
    answers = serve_waves(clock, engine, f"{label} pre-write", waves)
    check("pre-write", waves, answers)

    # write wave: inserts shaped like the corpus, tags from the same hash
    new_ids = np.arange(n, n + N_WRITES, dtype=np.int64)
    rows = (ref.vecs[rng.integers(0, n, N_WRITES)]
            + 0.5 * rng.standard_normal((N_WRITES, d)).astype(np.float32))
    new_codes = tag_codes(new_ids)
    recent = np.unique(np.concatenate([ids for ids, _ in answers[-1]]))
    victims = rng.choice(recent, min(len(recent), N_WRITES // 2),
                         replace=False)
    rest = np.setdiff1d(np.arange(n + N_WRITES), victims)
    victims = np.concatenate([victims, rng.choice(
        rest, N_WRITES - len(victims), replace=False)])

    def write_wave():
        for i, row, c in zip(new_ids, rows, new_codes):
            got = engine.insert(row, code_string(int(c)))
            if got != i:
                raise PhaseError(f"insert returned id {got}, expected {i}")
        for v in victims:
            engine.delete(int(v))

    clock.run(f"write wave: {N_WRITES} inserts + {N_WRITES} deletes",
              write_wave)
    ref.append(rows)
    codes = np.concatenate([codes, new_codes])
    ref.alive[victims] = False

    waves = make_waves(1, 4)
    answers = serve_waves(clock, engine, f"{label} post-write", waves)
    check("post-write (live delta)", waves, answers)

    clock.run("compact", engine.compact)
    waves = make_waves(4, 5)
    answers = batcher_waves(clock, engine, f"{label} post-compaction",
                            waves)
    check("post-compaction", waves, answers)
    report_launches(label, want_pallas=mesh is None)
    report_engine(label, engine, dev)


# --------------------------------------------------------------------- #
# phase B: graph states on the mtg corpus
# --------------------------------------------------------------------- #

def like_regex(pattern: str) -> str:
    return "".join(".*" if c == "%" else "." if c == "_" else re.escape(c)
                   for c in pattern)


def graph_phase(clock, dev, label: str) -> None:
    from repro.core.predicate import quote_literal
    from repro.core.vectormaton import VectorMatonConfig
    from repro.data.corpora import make_corpus, sample_patterns
    from repro.kernels import ops
    from repro.serve.engine import RetrievalEngine

    # a quarter of mtg: the host builds each HNSW graph one node at a
    # time in Python, minutes of host time for the full corpus
    scale, t_graph = 0.25, 100
    ops.reset_launch_stats()
    vecs, seqs = make_corpus("mtg", seed=0, scale=scale)
    engine = clock.run("index build", lambda: RetrievalEngine(
        vecs, seqs, VectorMatonConfig(T=t_graph, backend="jax")))
    stats = engine.index.stats()
    log(f"[{label}] mtg corpus at scale {scale} {vecs.shape}, T={t_graph}: "
        f"{stats['hnsw_states']} graph states, {stats['raw_states']} raw")
    if not stats["hnsw_states"]:
        raise PhaseError(f"{label}: T={t_graph} left no graph states")

    # request text -> reference membership over the sequences
    preds = {}
    for p in (sample_patterns(seqs, 1, 12, seed=1)
              + sample_patterns(seqs, 2, 16, seed=2)
              + sample_patterns(seqs, 3, 12, seed=3)):
        preds[quote_literal(p)] = lambda s, p=p: p in s
    pairs = sample_patterns(seqs, 2, 24, seed=4)
    for a, b in zip(pairs[0::2], pairs[1::2]):
        preds[f"{quote_literal(a)} AND {quote_literal(b)}"] = (
            lambda s, a=a, b=b: a in s and b in s)
        lk = f"%{a}%{b}%"
        preds[f"LIKE {quote_literal(lk)}"] = (
            lambda s, rx=like_regex(lk): re.fullmatch(rx, s, re.S)
            is not None)
    names = list(preds)
    member = {p: np.fromiter((f(s) for s in seqs), bool, len(seqs))
              for p, f in preds.items()}
    ref = Reference(vecs, lambda p: member[p])
    rng = np.random.default_rng(2)
    d = vecs.shape[1]
    waves = [(vecs[rng.integers(0, len(vecs), WAVE)]
              + 0.5 * rng.standard_normal((WAVE, d)).astype(np.float32),
              [names[(j * 7 + w) % len(names)] for j in range(WAVE)])
             for w in range(2)]
    answers = serve_waves(clock, engine, label, waves[:1])
    answers += batcher_waves(clock, engine, label, waves[1:])

    recs = []
    strategies = {}
    for w, ((qs, pats), ans) in enumerate(zip(waves, answers)):
        plan = engine.index.plan(pats)
        graph = np.zeros(len(pats), bool)
        for e in plan.entries:
            for s in e.sources:
                strategies[s.strategy] = strategies.get(s.strategy, 0) + 1
            if any(s.graph_states for s in e.sources):
                graph[e.requests] = True
        ex = np.flatnonzero(~graph)
        check_exact(ref, qs[ex], [pats[i] for i in ex],
                    [ans[i] for i in ex], K,
                    f"{label} wave {w} exact strategies")
        gi = np.flatnonzero(graph)
        if len(gi):
            recs.append((len(gi), recall_at_k(
                ref, qs[gi], [pats[i] for i in gi], [ans[i] for i in gi],
                K)))
    log(f"  strategies {label}: {json.dumps(strategies, sort_keys=True)}")
    n_graph = sum(c for c, _ in recs)
    if not n_graph:
        raise PhaseError(f"{label}: no request was answered by a graph")
    recall = sum(c * r for c, r in recs) / n_graph
    log(f"  recall@{K} {label}: {recall:.4f} over {n_graph} graph-answered "
        f"requests (CPU rehearsal: {PHASE_B_CPU_RECALL})")
    if recall < PHASE_B_CPU_RECALL - RECALL_SLACK:
        raise PhaseError(f"{label}: recall@{K} {recall:.4f} is below the "
                         f"CPU rehearsal's {PHASE_B_CPU_RECALL} less "
                         f"{RECALL_SLACK}")
    report_launches(label, want_pallas=True)
    report_engine(label, engine, dev)


# --------------------------------------------------------------------- #

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only phase C, the sharded path")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, Pallas in interpret mode")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["REPRO_IMPL"] = "pallas"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.kernels import ops
    from repro.launch.compile_cache import place_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "run with --rehearse for the CPU rehearsal", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} devices asked for, "
              f"{len(devices)} found", file=sys.stderr)
        return 1
    log(f"device_kind {dev.device_kind!r} platform {dev.platform} "
        f"count {len(devices)}")
    log(f"compile cache {place_compile_cache()}")
    impl, interpret = ops.default_impl(), ops.default_interpret()
    log(f"kernels impl={impl} interpret={interpret}")
    if not args.rehearse and (impl != "pallas" or interpret):
        print("chip_smoke: the TPU must run the compiled Pallas kernels",
              file=sys.stderr)
        return 1
    clock = CompileClock(jax)
    n = 4096 if args.rehearse else 1 << 20
    t0 = time.perf_counter()
    if args.chips > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(data=args.chips, model=1)
        clock.run("phase C", lambda: scale_phase(clock, dev, "phase C", n,
                                                 mesh=mesh))
    else:
        clock.run("phase A", lambda: scale_phase(clock, dev, "phase A", n))
        clock.run("phase B", lambda: graph_phase(clock, dev, "phase B"))
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
