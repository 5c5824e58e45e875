"""VectorMaton — pattern-constrained ANNS index (paper §4).

Build (Algorithm 3 Build):
  1. ESAM over the sequence collection, with online vector-ID propagation.
  2. Reverse-topological sweep over the transition DAG.  For each state u:
       - index-reuse: inherit(u) = the direct successor with the largest
         covered set; base(u) = V_u \\ V_inherit(u)   (Lemma 4 exact cover —
         coverage is defined recursively along the inheritance chain, so the
         union of base sets along u's chain is exactly V_u);
       - skip-build: |base(u)| < T  ->  raw ID set (brute-force at query
         time); otherwise an HNSW graph over base(u).

Query (Algorithm 3 Query, extended to boolean predicates): handled by the
predicate compiler + planner/executor runtime (core/predicate.py,
core/packed.py, DESIGN.md §3).  At finalize time the chain structure and
per-state indexes are flattened into struct-of-arrays form (CSR base-ID
segments + padded graph matrices, uploaded to device once); at query time
each request's predicate — a plain CONTAINS pattern or an AND/OR/NOT/LIKE
string — compiles to per-disjunct execution sources (chain / scan /
filtered-graph / residual), identical predicates coalesce, and a batched
executor answers all brute-forced candidate sets with ONE segmented fused
distance+top-k launch, all shared graphs with vmapped (optionally
bitmap-filtered) beam searches, and residual LIKEs with an over-fetch +
host-verify loop.  ``query`` is the single-request special case of
``query_batch``.

Maintenance (paper §5, extended by DESIGN.md §4 "Write path"): online
insert extends the automaton and patches the affected base indexes without
a global rebuild — and without invalidating the packed query runtime: the
flattened ``PackedRuntime`` is an immutable *generation*, inserts land in
its append-only delta (growable vector buffer + per-state delta ID lists),
and a threshold-triggered *compaction* folds delta + tombstone GC into a
fresh generation swapped in behind the readers.  Deletes are lazy marks
filtered at query time and physically GC'd at compaction.

The build's per-state passes (inheritance, base sets, the CSR) are
array operations over the finalized automaton's flattened id lists;
only the graphs of states whose base set reaches ``T`` are built one by
one, on a thread pool (NumPy releases the GIL inside distance batches).
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..serve.telemetry import phase_span
from .esam import ESAM, lists_csr
from .hnsw import HNSW
from .packed import KIND_GRAPH, KIND_NONE, KIND_RAW, PackedRuntime, \
    QueryPlan, VectorStore, ranges
from .planner import AdaptivePlanner
from .predicate import CompiledPredicate, Predicate, as_predicate, \
    compile_predicate

_RAW = KIND_RAW
_HNSW = KIND_GRAPH


@dataclass
class VectorMatonConfig:
    T: int = 200                 # skip-build threshold (paper default)
    M: int = 16                  # HNSW max degree
    ef_con: int = 200            # HNSW construction beam
    metric: str = "l2"
    reuse: bool = True           # index-reuse strategy (ablation switch)
    skip_build: bool = True      # skip-build strategy (ablation switch)
    seed: int = 0
    backend: str = "numpy"       # 'numpy' host path | 'jax' device path
    # 'sq8' (default): int8 scan + certified fp32 rerank on the jax scan
    # path — provably equal to the fp32 scan (batches whose certificate
    # fails escalate to it); 'none': fp32 scan only.  Ineligible shapes
    # (see kernels.quant.sq8_supported) fall back to fp32 transparently.
    quantize: str = "sq8"
    accum: str = "f32"           # 'bf16': bf16 MXU operands, f32 accum
    # write path (DESIGN.md §4): fold the delta into a fresh generation
    # once it holds max(compact_min_inserts, compact_ratio · |base|)
    # inserts; auto_compact=False leaves compaction to explicit compact()
    compact_min_inserts: int = 256
    compact_ratio: float = 0.25
    auto_compact: bool = True
    # typed attribute schema (DESIGN.md §9): field name -> 'tag' | 'numeric'.
    # Declared fields are indexed at freeze/compact into per-attribute
    # sorted-ID CSR segments and become queryable via comparison syntax
    # ("genre = 'rock' AND price < 10"); undeclared fields raise at
    # predicate compile time.  None = no structured attributes.
    schema: Optional[Dict[str, str]] = None
    # strategy arbitration (DESIGN.md §11): 'adaptive' scores every legal
    # strategy per conjunction source with the cost model and folds
    # executor feedback at wave heads; 'static' keeps every legacy
    # compile-time decision — the bit-exactness parity oracle.  Adaptive
    # never changes WHAT a plan returns, only WHICH exact strategy runs.
    plan_mode: str = "adaptive"


@dataclass
class _StateIndex:
    kind: int                    # _RAW | _HNSW
    raw_ids: Optional[np.ndarray] = None
    graph: Optional[HNSW] = None

    @property
    def n_indexed(self) -> int:
        return (len(self.raw_ids) if self.kind == _RAW else len(self.graph))


@contextlib.contextmanager
def _collector_paused():
    """Python's cyclic collector off for a block.  The automaton is one
    dict and one list per state and holds no cycles, but each of the
    collector's passes while it grows walks every object built so far
    (a third of the build's time at 8K protein rows)."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def csr_difference(a_ptr, a_ids, b_ptr, b_ids
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Row by row ``a \\ b`` of two CSRs with the same rows, ids strictly
    increasing within a row, each row of b inside the same row of a: one
    ``searchsorted`` of b's (row, id) keys among a's, which are sorted."""
    rows = len(a_ptr) - 1
    width = int(a_ids.max()) + 1 if len(a_ids) else 1
    a_len, b_len = np.diff(a_ptr), np.diff(b_ptr)
    key_a = np.repeat(np.arange(rows, dtype=np.int64), a_len) * width + a_ids
    key_b = np.repeat(np.arange(rows, dtype=np.int64), b_len) * width + b_ids
    keep = np.ones(len(a_ids), dtype=bool)
    keep[np.searchsorted(key_a, key_b)] = False
    ptr = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum(a_len - b_len, out=ptr[1:])
    return ptr, a_ids[keep]


def pick_inherit(lens: np.ndarray, dst: np.ndarray,
                 size: np.ndarray) -> np.ndarray:
    """Per state, the direct successor with the largest V (the first in
    transition order among equals), -1 where no successor holds an id.
    ``lens``/``dst``: transitions per state and their targets
    (``ESAM.transitions``); ``size``: |V| of each target."""
    out = np.full(len(lens), -1, dtype=np.int64)
    if len(dst) == 0:
        return out
    src = np.repeat(np.arange(len(lens), dtype=np.int64), lens)
    order = np.lexsort((-size, src))            # stable: ties keep order
    has = lens > 0
    first = order[(np.cumsum(lens) - lens)[has]]
    out[has] = np.where(size[first] > 0, dst[first], -1)
    return out


class StateIndexes:
    """Every state's index: its base set V_u \\ V_inherit(u) (Lemma 4)
    in one CSR (``kind``, ``ptr``, ``ids``), and an HNSW graph for each
    graph state, whose segment is the graph's node list.  The write path
    changes single states (``set_raw``, ``set_graph``, ``grow``);
    ``flatten`` folds them into a fresh CSR.  ``state_index[u]`` reads
    one state as a ``_StateIndex`` (None where the state has none)."""

    def __init__(self, kind: np.ndarray, ptr: np.ndarray, ids: np.ndarray,
                 graphs: Optional[Dict[int, HNSW]] = None) -> None:
        self.kind = np.asarray(kind, dtype=np.int8)
        self.ptr = np.asarray(ptr, dtype=np.int64)
        self.ids = np.asarray(ids, dtype=np.int64)
        self.graphs: Dict[int, HNSW] = dict(graphs or {})
        self.patched: Dict[int, np.ndarray] = {}    # raw sets since flatten

    def __len__(self) -> int:
        return len(self.kind)

    def __getitem__(self, u: int) -> Optional[_StateIndex]:
        k = int(self.kind[u])
        if k == _HNSW:
            return _StateIndex(_HNSW, graph=self.graphs[u])
        if k == _RAW:
            return _StateIndex(_RAW, raw_ids=self.raw(u))
        return None

    def raw(self, u: int) -> np.ndarray:
        seg = self.patched.get(u)
        return (seg if seg is not None
                else self.ids[self.ptr[u]:self.ptr[u + 1]])

    def set_raw(self, u: int, ids) -> None:
        self.kind[u] = _RAW
        self.graphs.pop(u, None)
        self.patched[u] = np.asarray(ids, dtype=np.int64)

    def set_graph(self, u: int, graph: HNSW) -> None:
        self.kind[u] = _HNSW
        self.patched.pop(u, None)
        self.graphs[u] = graph

    def grow(self, n: int) -> None:
        """States ``len(self)`` .. n-1, with no index yet."""
        add = n - len(self.kind)
        if add > 0:
            self.kind = np.concatenate(
                [self.kind, np.full(add, KIND_NONE, np.int8)])
            self.ptr = np.concatenate(
                [self.ptr, np.full(add, self.ptr[-1], np.int64)])

    def drop_ids(self, gone: np.ndarray) -> None:
        """Remove ``gone`` from every raw set (tombstone GC)."""
        self.flatten()
        dead = np.isin(self.ids, gone)
        if dead.any():
            row = np.repeat(np.arange(len(self.kind)), np.diff(self.ptr))
            lens = np.bincount(row[~dead], minlength=len(self.kind))
            self.ptr = np.zeros_like(self.ptr)
            np.cumsum(lens, out=self.ptr[1:])
            self.ids = self.ids[~dead]

    def flatten(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(kind, ptr, ids)`` with every change folded in and each graph
        state's segment read from its graph (inserts add nodes to it): a
        copy of ``kind``, and new arrays wherever a segment changed."""
        seg = dict(self.patched)
        for u, g in self.graphs.items():
            seg[u] = np.asarray(g.ids, dtype=np.int64)
        if seg:
            us = np.fromiter(sorted(seg), np.int64, len(seg))
            old = np.diff(self.ptr)
            lens = old.copy()
            lens[us] = [len(seg[int(u)]) for u in us]
            ptr = np.zeros_like(self.ptr)
            np.cumsum(lens, out=ptr[1:])
            ids = np.empty(int(ptr[-1]), dtype=np.int64)
            keep = np.ones(len(lens), dtype=bool)
            keep[us] = False
            ids[ranges(ptr[:-1][keep], lens[keep])] = self.ids[
                ranges(self.ptr[:-1][keep], old[keep])]
            for u in us.tolist():
                ids[ptr[u]:ptr[u + 1]] = seg[u]
            self.ptr, self.ids = ptr, ids
            self.patched = {}
        return self.kind.copy(), self.ptr, self.ids


class VectorMaton:
    """The paper's index.  ``vectors``: (n, d) global table; ``sequences``:
    list of symbol sequences (strings or lists)."""

    def __init__(self, vectors: np.ndarray, sequences: Sequence[Sequence],
                 config: Optional[VectorMatonConfig] = None,
                 workers: int = 1,
                 attributes: Optional[Sequence[Dict]] = None) -> None:
        self.config = config or VectorMatonConfig()
        for f, kind in (self.config.schema or {}).items():
            if kind not in ("tag", "numeric"):
                raise ValueError(
                    f"schema field {f!r}: unknown type {kind!r} "
                    f"(expected 'tag' or 'numeric')")
        self.vectors = vectors                   # adopted into a VectorStore
        self.esam = ESAM()
        self.deleted: set = set()
        self.sequences: List = list(sequences)   # LIKE residual verification
        if attributes is not None and len(attributes) != len(sequences):
            raise ValueError(
                f"attributes ({len(attributes)}) must align with "
                f"sequences ({len(sequences)})")
        # one dict per record; schema-declared fields are type-coerced so
        # the frozen sorted arrays and host verification agree exactly
        self.attributes: List[Dict] = [
            self._norm_attrs(a) for a in (attributes or [])]
        self.attributes.extend({} for _ in range(
            len(self.sequences) - len(self.attributes)))
        self._lock = threading.Lock()
        self._compact_lock = threading.Lock()
        self.runtime_builds = 0                  # full re-flatten count
        self.n_compactions = 0
        self._gen_seq = 0                        # next generation number
        # owned by the index, NOT the runtime: cost-model feedback and
        # measured winners survive compactions (DESIGN.md §11).  Raises
        # on an unknown plan_mode before any build work happens.
        self.planner = AdaptivePlanner(self.config.plan_mode)
        # milliseconds of each build phase (maintenance_stats time_*)
        self.build_times: Dict[str, float] = {
            "build_esam_ms": 0.0, "build_states_ms": 0.0,
            "build_runtime_ms": 0.0}
        with phase_span("build/esam"), _collector_paused():
            t0 = time.perf_counter()
            for s in sequences:
                self.esam.add_sequence(s)
            self.esam.finalize()
            self.build_times["build_esam_ms"] += (
                time.perf_counter() - t0) * 1e3
        with phase_span("build/state_indexes"):
            t0 = time.perf_counter()
            self._build_state_indexes(workers=workers)
            self.build_times["build_states_ms"] += (
                time.perf_counter() - t0) * 1e3
        self._runtime: Optional[PackedRuntime] = self._build_runtime()

    def _norm_attrs(self, attrs: Optional[Dict]) -> Dict:
        """Coerce schema-declared fields (numeric -> float, tag -> str) so
        frozen sorted arrays, delta evaluation, and host verification all
        compare the same representation; undeclared keys pass through."""
        out = dict(attrs or {})
        for f, kind in (self.config.schema or {}).items():
            if f in out:
                out[f] = float(out[f]) if kind == "numeric" else str(out[f])
        return out

    # ------------------------------------------------------------------ #
    # vector storage (growable, capacity-doubling — DESIGN.md §4)
    # ------------------------------------------------------------------ #

    @property
    def vectors(self) -> np.ndarray:
        """Live (n, d) view of the growable vector table.  Re-fetched by
        readers after every insert (a buffer reallocation moves it)."""
        return self._vec_store.view

    @vectors.setter
    def vectors(self, table: np.ndarray) -> None:
        self._vec_store = VectorStore(table)

    # ------------------------------------------------------------------ #
    # index construction (Algorithm 3 lines 17-21)
    # ------------------------------------------------------------------ #

    def _inherit_of(self, states: Optional[Sequence[int]] = None
                    ) -> np.ndarray:
        """Index reuse: each of ``states``' (every state's by default)
        direct successor with the largest covered set (== |V_succ|), -1
        without reuse."""
        esam = self.esam
        if not self.config.reuse:
            n = esam.num_states if states is None else len(states)
            return np.full(n, -1, dtype=np.int64)
        ptr, dst = esam.transitions(states)
        if esam.id_ptr is not None:
            size = np.diff(esam.id_ptr)[dst]
        else:
            size = np.fromiter((len(esam.ids[v]) for v in dst.tolist()),
                               np.int64, len(dst))
        return pick_inherit(np.diff(ptr), dst, size)

    def _graph_states(self, lens: np.ndarray) -> np.ndarray:
        """Which base sets get an HNSW graph (skip-build below T)."""
        big = lens > 0
        if self.config.skip_build:
            big &= lens >= self.config.T
        return big

    def _new_graph(self, u: int, base: np.ndarray) -> HNSW:
        cfg = self.config
        g = HNSW(self.vectors, M=cfg.M, ef_con=cfg.ef_con, metric=cfg.metric,
                 seed=cfg.seed + u)
        g.build(base)
        return g

    def _parallel_build(self, jobs: List[Tuple[int, np.ndarray]],
                      workers: int) -> Dict[int, HNSW]:
        """An HNSW over each (state, base set); ``workers`` threads build
        them side by side (the paper's parallel construction, §4.3: base
        sets depend only on V sets, so the graphs are independent, and
        NumPy releases the GIL inside distance batches)."""
        if workers <= 1 or len(jobs) <= 1:
            return {u: self._new_graph(u, b) for u, b in jobs}
        with ThreadPoolExecutor(workers) as pool:
            graphs = list(pool.map(lambda job: self._new_graph(*job), jobs))
        return {u: g for (u, _), g in zip(jobs, graphs)}

    def _build_state_indexes(self, workers: int = 1) -> None:
        """Algorithm 3 lines 17-21 as array passes over the finalized
        ESAM: every state's inheritance, then every base set
        V_u \\ V_inherit(u) in one ``csr_difference`` over the flattened
        id lists, then a graph for each base set that reaches ``T``."""
        esam = self.esam
        n = esam.num_states
        self.inherit = self._inherit_of()
        has = self.inherit >= 0
        h = np.where(has, self.inherit, 0)
        h_len = np.where(has, np.diff(esam.id_ptr)[h], 0)
        h_ptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(h_len, out=h_ptr[1:])
        ptr, ids = csr_difference(
            esam.id_ptr, esam.id_data, h_ptr,
            esam.id_data[ranges(esam.id_ptr[h], h_len)])
        graph = self._graph_states(np.diff(ptr))
        kind = np.where(graph, _HNSW, _RAW).astype(np.int8)
        jobs = [(u, ids[ptr[u]:ptr[u + 1]])
                for u in np.nonzero(graph)[0].tolist()]
        self.state_index = StateIndexes(kind, ptr, ids,
                                        self._parallel_build(jobs, workers))

    # ------------------------------------------------------------------ #
    # query processing (Algorithm 3 Query)
    # ------------------------------------------------------------------ #

    def _chain(self, state: int) -> List[int]:
        out = []
        u = state
        while u != -1:
            out.append(u)
            u = self.inherit[u]
        return out

    def _build_runtime(self) -> PackedRuntime:
        """One full re-flatten = one generation.  Counted: the churn
        acceptance criterion is builds == compactions, not inserts."""
        with phase_span("build/runtime"):
            t0 = time.perf_counter()
            rt = PackedRuntime.build(self, generation=self._gen_seq)
            self.build_times["build_runtime_ms"] += (
                time.perf_counter() - t0) * 1e3
        self._gen_seq += 1
        self.runtime_builds += 1
        return rt

    @property
    def runtime(self) -> PackedRuntime:
        """The current generation.  Inserts do NOT invalidate it — they
        land in its delta; only a compaction (or a checkpoint restore)
        produces a new one."""
        if self._runtime is None:
            self._runtime = self._build_runtime()
        return self._runtime

    def snapshot(self) -> PackedRuntime:
        """The current immutable generation (plus its delta).  Readers
        take one snapshot per batch: a plan compiled against it executes
        against it, so a concurrent compaction swap can never split plan
        and execute across generations (execute() enforces this)."""
        return self.runtime

    def _refresh_runtime(self) -> None:
        """Invalidate wholesale (checkpoint restore); the ordinary write
        path goes through the delta + compact() instead."""
        self._runtime = None

    _PRED_CACHE_MAX = 256        # entries can hold O(n) id arrays/masks

    def compile(self, pattern,
                runtime: Optional[PackedRuntime] = None) -> CompiledPredicate:
        """Lower a request pattern — a plain CONTAINS pattern, a predicate
        string (``"ab AND NOT LIKE 'c%d'"``), or a ``Predicate`` — to
        executable sources against ``runtime`` (default: current
        snapshot).  Compiled predicates are cached per (runtime, delta
        version): an insert bumps the delta version so stale plans (whose
        delta id lists miss the newest writes) recompile; deletes are
        tombstone-filtered at execute time and don't.  The cache is
        bounded: compiled boolean sources carry O(n) id arrays, so a
        serving stream of ever-distinct predicates must not grow it
        without bound.  Eviction is LRU with a stale-first sweep: a hit
        refreshes recency (hot predicates survive a thrash of distinct
        cold ones), and entries stamped with an outdated delta version —
        dead weight that can never hit again — are purged before any
        live entry is evicted."""
        pred = as_predicate(pattern)
        rt = runtime if runtime is not None else self.runtime
        key = pred.key()
        version = rt.delta.version
        planner = self.planner
        hit = rt._pred_cache.get(key)
        if hit is not None:
            if (hit[0] == version
                    and hit[2] == planner.winner_for(hit[1].key, version)):
                rt._pred_cache.pop(key)          # re-insert: LRU refresh
                rt._pred_cache[key] = hit
                return hit[1]
            # version-stale, or the planner measured a winning strategy
            # after this entry compiled (residual yield collapse,
            # cost-model demotion) — recompile so the plan replays it
            del rt._pred_cache[key]
        cp = compile_predicate(pred, self.esam, rt, planner=planner)
        if len(rt._pred_cache) >= self._PRED_CACHE_MAX:
            # one pass: purge version-stale entries (dead weight that can
            # never hit again), and only if that freed nothing evict the
            # LRU head.  The old two-step (purge loop THEN an
            # unconditional `while >= MAX` pop) re-checked capacity after
            # the purge and popped the oldest LIVE entry even when the
            # purge had already made room — evicting a just-refreshed hot
            # entry on insertion at exactly-full capacity.
            stale = [k for k, (v, *_rest) in rt._pred_cache.items()
                     if v != version]
            for stale_key in stale:
                del rt._pred_cache[stale_key]
            if not stale:
                rt._pred_cache.pop(next(iter(rt._pred_cache)))
        rt._pred_cache[key] = (version, cp,
                               planner.winner_for(cp.key, version))
        return cp

    def plan(self, patterns: Sequence,
             runtime: Optional[PackedRuntime] = None) -> QueryPlan:
        """Compile each request's predicate and coalesce identical
        predicates into one plan entry each (the host planner half).

        Wave head: the ONLY point where executor feedback folds into the
        cost model (planner.absorb), so a plan is compiled against one
        frozen cost state and generation-stamped plans stay immutable —
        single-chip, pipelined (engine.plan_batch lands here) and sharded
        planning all share this cadence (DESIGN.md §11)."""
        rt = runtime if runtime is not None else self.runtime
        self.planner.absorb()
        return rt.plan([self.compile(p, rt) for p in patterns])

    def query(self, v_q: np.ndarray, pattern, k: int,
              ef_search: int = 64) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k (distances, global ids) among vectors whose sequence
        satisfies ``pattern`` — a CONTAINS pattern, predicate string, or
        ``Predicate`` AST.  Empty pattern == unconstrained ANN.
        Single-request special case of ``query_batch``."""
        return self.query_batch(
            np.asarray(v_q, dtype=np.float32)[None, :], [pattern], k,
            ef_search=ef_search)[0]

    def query_batch(self, queries: np.ndarray,
                    patterns: Sequence, k: int,
                    ef_search: int = 64
                    ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Batched query path: compile+plan once per distinct predicate,
        then one segmented device sweep for all brute-forced candidate
        sets + one vmapped beam search per shared graph (+ residual
        verification loops for multi-segment LIKE).  Returns
        [(dists, ids)] per request.  Plans and executes against ONE
        runtime snapshot, so a mid-batch compaction swap cannot mix
        generations."""
        rt = self.snapshot()
        t0 = time.perf_counter()
        plan = self.plan(patterns, rt)
        rt.wave_times["plan_ms"] += (time.perf_counter() - t0) * 1e3
        return rt.execute(queries, plan, k, ef_search=ef_search)

    # ------------------------------------------------------------------ #
    # maintenance (paper §5)
    # ------------------------------------------------------------------ #

    def insert(self, vector: np.ndarray, sequence: Sequence,
               attributes: Optional[Dict] = None) -> int:
        """Online insert: extend automaton; patch base indexes of affected
        states.  New states index only the new ID (their V starts at {i});
        clones rebuild their base against the current best successor —
        correctness over size-optimality, as in the paper's online update.

        Write path (DESIGN.md §4): the vector lands in the growable table
        (amortized O(d) append — no O(N) concatenate) and the id is logged
        into the current generation's delta at exactly the states the
        affected-state logic patches, so the frozen ``PackedRuntime`` —
        including its device-resident arrays — survives untouched.
        Queries merge base ∪ delta; the re-flatten cost moves to the next
        compaction, triggered here once the delta crosses the configured
        threshold (or immediately on a raw→graph promotion, which the
        frozen generation cannot see)."""
        i = self.esam.num_sequences
        self.sequences.append(sequence)
        # the delta row's attributes ride the live list (the runtime
        # shares it); attribute leaves pick them up at compile time via
        # the post-freeze scan, so no per-state delta record is needed
        self.attributes.append(self._norm_attrs(attributes))
        self._vec_store.append(vector)
        view = self.vectors
        for g in self.state_index.graphs.values():
            g.vectors = view                     # re-point at the live view
        rt = self._runtime
        delta = rt.delta if rt is not None else None
        if rt is not None:
            rt.vectors = view
        old_n = self.esam.num_states
        self.esam.add_sequence(sequence)
        n = self.esam.num_states
        ids = self.esam.ids
        # new states (created by this sequence): fresh indexes.  They are
        # past the generation's state watermark, so the compiler answers
        # them from their live ESAM V sets — no delta record needed.
        self.inherit = np.concatenate(
            [self.inherit, np.full(n - old_n, -1, np.int64)])
        si = self.state_index
        si.grow(n)
        clones = np.asarray([u for u in range(old_n, n) if len(ids[u]) > 1],
                            dtype=np.int64)
        for u in range(old_n, n):
            if len(ids[u]) <= 1:
                si.set_raw(u, [i])
        if len(clones):
            # clones: inheritance against current successors, base sets
            # by the build's own difference
            inherit = self._inherit_of(clones.tolist())
            self.inherit[clones] = inherit
            ptr, base = csr_difference(
                *lists_csr([ids[u] for u in clones.tolist()]),
                *lists_csr([ids[h] if h >= 0 else ()
                            for h in inherit.tolist()]))
            graph = self._graph_states(np.diff(ptr))
            for j, u in enumerate(clones.tolist()):
                seg = base[ptr[j]:ptr[j + 1]]
                if not graph[j]:
                    si.set_raw(u, seg)
                    continue
                si.set_graph(u, self._new_graph(u, seg))
                if delta is not None:
                    # a graph born after the freeze: delete() must reach
                    # it, and compaction should fold it into service
                    delta.fresh_graph_states.add(u)
        # affected old states: those whose V gained i
        for u in range(old_n):
            vu = ids[u]
            if not vu or vu[-1] != i:
                continue
            h = int(self.inherit[u])
            if h != -1 and ids[h] and ids[h][-1] == i:
                continue  # coverage flows up the chain
            kind = si.kind[u]
            if kind == KIND_NONE:
                si.set_raw(u, [i])
            elif kind == _RAW:
                raw = np.append(si.raw(u), i)
                if (not self.config.skip_build
                        or len(raw) >= 4 * self.config.T):
                    si.set_graph(u, self._promote(raw, u))
                    if delta is not None:
                        delta.fresh_graph_states.add(u)
                else:
                    si.set_raw(u, raw)
            else:
                si.graphs[u].add(i)
                if delta is not None:
                    # keep the delete fan-out map fresh incrementally; a
                    # post-freeze graph (promotion/clone) is absent from
                    # graph_objs and handled via fresh_graph_states
                    m = rt._id_graph_states
                    if m is not None and u in rt.graph_objs:
                        m.setdefault(i, []).append(u)
            if delta is not None:
                delta.record(u, i)
        if delta is not None:
            delta.pending += 1
            delta.inserted.append(i)             # replication delta log
            delta.version += 1                   # invalidates cached plans
        if self.config.auto_compact:
            self.maybe_compact()
        return i

    def maybe_compact(self) -> bool:
        """Threshold / size-ratio compaction trigger: fold the delta once
        it holds max(compact_min_inserts, compact_ratio · |frozen base|)
        inserts, or immediately after a raw→graph promotion (the promoted
        graph is invisible to the frozen generation until folded)."""
        rt = self._runtime
        if rt is None:
            return False
        d = rt.delta
        if d.empty and not d.fresh_graph_states:
            return False
        threshold = max(self.config.compact_min_inserts,
                        int(self.config.compact_ratio * d.n_base))
        if d.fresh_graph_states or d.pending >= threshold:
            self.compact()
            return True
        return False

    def compact(self) -> PackedRuntime:
        """Fold the delta and GC tombstones into a fresh generation.

        Built off the read path: the current generation keeps serving
        while the new one flattens — readers holding a snapshot stay on a
        consistent (generation, delta) view — and the swap is one
        reference assignment.  Tombstone GC drops deleted ids from every
        raw base set and rebuilds (or demotes) graphs whose tombstone
        fraction crossed ``_GRAPH_GC_FRAC``; the ids stay in ``deleted``
        because the ESAM's V sets cannot shrink."""
        with self._compact_lock:
            if self.deleted:
                self._gc_tombstones()
            new_rt = self._build_runtime()
            self._runtime = new_rt
            self.n_compactions += 1
            return new_rt

    _GRAPH_GC_FRAC = 0.5

    def _gc_tombstones(self) -> None:
        si = self.state_index
        si.drop_ids(np.fromiter(self.deleted, dtype=np.int64))
        for u, g in list(si.graphs.items()):
            dead = g._deleted & set(int(x) for x in g.ids)
            if len(dead) <= self._GRAPH_GC_FRAC * max(1, len(g.ids)):
                continue
            live = np.asarray([x for x in g.ids if x not in dead],
                              dtype=np.int64)
            if len(live) < max(1, self.config.T):
                si.set_raw(u, live)
            else:
                si.set_graph(u, self._new_graph(u, live))

    def maintenance_stats(self) -> Dict[str, int]:
        """Write-path accounting (generation / delta / compaction counters
        plus the growable-buffer copy trace — bench_churn's acceptance
        signals: builds == compactions, O(log n) reallocations) and the
        device-execution trace (DESIGN.md §3): kernel launch + retrace
        counters (``launch_*``) and per-class host→device traffic bytes
        (``traffic_*``) that the benchmark gate and the retrace-regression
        test read."""
        from ..kernels import ops
        rt = self._runtime
        out = {
            "generation": rt.generation if rt is not None else -1,
            "delta_pending": rt.delta.pending if rt is not None else 0,
            "delta_version": rt.delta.version if rt is not None else 0,
            "runtime_builds": self.runtime_builds,
            "compactions": self.n_compactions,
            "vector_reallocations": self._vec_store.reallocations,
            "vector_bytes_copied": self._vec_store.bytes_copied,
            "deleted": len(self.deleted),
            "esam_symbols": self.esam.total_symbols,
        }
        for key, val in self.build_times.items():
            out[f"time_{key}"] = val
        for key, val in ops.launch_stats().items():
            out[f"launch_{key}"] = val
        if rt is not None:
            for key, val in rt.traffic.items():
                out[f"traffic_{key}"] = val
            # SQ8 scan-path accounting (certified vs escalated vs
            # fell-back batches) and the per-wave wall-clock breakdown.
            # Launch time is trace+dispatch (device dispatch is async);
            # the merge wave absorbs the device sync.
            for key, val in rt.sq8_stats.items():
                out[f"sq8_{key}"] = val
            for key, val in rt.wave_times.items():
                out[f"time_{key}"] = val
            for key, val in rt.residual_stats.items():
                out[f"residual_{key}"] = val
        # adaptive-planner trace (DESIGN.md §11): estimates vs observed,
        # strategy switches, cache-replayed winners
        out.update(self.planner.stats())
        return out

    def _promote(self, raw_ids: np.ndarray, u: int) -> HNSW:
        """Raw -> HNSW promotion once a raw set outgrows 4*T (paper §5): the
        brute-force sweep over the set now costs more than a graph search,
        so rebuild it as a graph against the packed runtime."""
        g = self._new_graph(u, raw_ids)
        for vid in self.deleted & set(int(x) for x in raw_ids):
            g.mark_deleted(vid)
        return g

    def delete(self, vector_id: int) -> None:
        """Lazy deletion (paper §5): mark and filter at query time.  The
        tombstone is propagated into every per-state graph whose node set
        contains the ID (so graph searches skip it in-scan instead of
        returning it and crowding out live candidates before the
        query-level filter), into graphs promoted since the generation
        froze, and into the device-resident mask.  Physical removal
        happens at the next compaction's tombstone GC."""
        vid = int(vector_id)
        self.deleted.add(vid)
        rt = self.runtime
        for u in rt.graph_states_of(vid):
            rt.graph_objs[u].mark_deleted(vid)
        for u in rt.delta.fresh_graph_states:
            idx = self.state_index[u]
            if (idx is not None and idx.kind == _HNSW
                    and vid in idx.graph.ids):
                idx.graph.mark_deleted(vid)
        rt.mark_deleted(vid)

    # ------------------------------------------------------------------ #
    # accounting / serialization
    # ------------------------------------------------------------------ #

    def size_entries(self) -> int:
        """Paper's index-size metric: stored ID entries + graph edge slots +
        automaton states/transitions."""
        si = self.state_index
        kind, ptr, _ = si.flatten()
        raw = int(np.diff(ptr)[kind == _RAW].sum())
        return (self.esam.num_states + self.esam.num_transitions + raw
                + sum(g.size_entries for g in si.graphs.values()))

    def stats(self) -> Dict[str, int]:
        kind = self.state_index.kind
        return {
            "states": self.esam.num_states,
            "transitions": self.esam.num_transitions,
            "total_id_entries": self.esam.total_id_entries(),
            "raw_states": int((kind == _RAW).sum()),
            "hnsw_states": int((kind == _HNSW).sum()),
            "size_entries": self.size_entries(),
            "total_symbols": self.esam.total_symbols,
        }

    def save(self, path: str, extra_meta: Optional[Dict] = None) -> None:
        from ..distributed.checkpoint import save_vectormaton
        save_vectormaton(self, path, extra_meta=extra_meta)

    @classmethod
    def load(cls, path: str) -> "VectorMaton":
        from ..distributed.checkpoint import load_vectormaton
        return load_vectormaton(cls, path)
