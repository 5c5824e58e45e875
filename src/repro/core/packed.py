"""Packed query runtime — the planner/executor substrate (DESIGN.md §3).

The build-time structures (ESAM dicts, per-state ``_StateIndex`` objects,
``HNSW`` instances) are pointer-rich host objects: right for incremental
construction, wrong for the hot query path.  At finalize time this module
flattens them into struct-of-arrays form:

  * ``kind``      (n_states,)  int8   — NONE / RAW / GRAPH per state;
  * ``inherit``   (n_states,)  int64  — inheritance-chain successor (-1 end);
  * ``base_lo`` / ``base_hi`` (n_states,) int64 + ``base_ids`` (Σ|base|,)
    int64 — *every* state's base-ID segment (raw and graph states alike)
    at ``base_ids[base_lo[u]:base_hi[u]]``, so a chain walk is a handful
    of array reads and the union of a chain's segments is exactly
    V_state (Lemma 4).  Segments lie in ``chain_layout`` order: each
    heavy path of the inheritance forest is one contiguous run, so a
    chain of hundreds of states covers a few runs, not hundreds;
  * per-graph padded neighbour matrices (``HNSW.pack()``) kept by state.

Query execution splits into a host **planner** and a device-resident
**executor** over *compiled predicates* (core/predicate.py, DESIGN.md §3):

  * ``PackedRuntime.plan`` coalesces requests with identical predicate keys
    into one ``PlanEntry`` carrying the predicate's compiled sources —
    chain covers as CSR *descriptor ranges*, explicit id sets, composed
    membership masks, residual verifiers — no per-state Python objects
    survive into execution;
  * ``PackedRuntime.execute`` answers the whole batch touching the host
    only for planning integers and the final (k,) results: ALL
    brute-force candidate sets go through ONE descriptor-driven segmented
    distance+top-k launch (``ops.topk_segmented_desc`` — frozen covers
    resolve against the device-resident CSR, zero candidate-id upload;
    only post-watermark delta tails ship ids + rows), graph states run
    ONE fused beam launch per size bucket vmapped over (graph, query)
    pairs (conjunction bitmaps stacked per distinct mask; tombstone
    over-fetch clamped at the beam's ef capacity, past which the resident
    deleted bitmap filters in-loop), ``residual`` sources run an
    over-fetch + exact host-side verification loop until k verified hits,
    and the per-request merge — dedup across OR disjuncts, tombstone
    filter, cut to k — folds on device (``ops.merge_topk_device``) for
    requests whose parts are all launch rows.  Every dynamic dimension is
    power-of-two bucketed, so steady-state serving replays a fixed
    executable set (launch/retrace counters in ``kernels.ops``, traffic
    counters in ``PackedRuntime.traffic``).

Device placement (DESIGN.md §2): ``to_device()`` uploads the vector table,
the base-ID CSR, a deleted-mask, and the graph matrices (per state and as
size-bucketed stacks) exactly once; queries afterwards ship only the
plan's integers, the query rows, and the bounded delta tail.  The host
backend runs the same plan with NumPy kernels and a NumPy merge — the
bit-exactness oracle for every device stage (the ``use_descriptors`` /
``fuse_graphs`` / ``device_merge`` toggles force the legacy paths for
parity tests).

Write path (DESIGN.md §4): a built ``PackedRuntime`` is an immutable
**generation**.  Inserts never touch its arrays — they land in the
attached ``DeltaRuntime`` (per-state delta ID lists plus a growable
``VectorStore`` owned by the VectorMaton), and every execution strategy
merges delta candidates: chain/scan segments get the delta IDs appended
to their brute-forced sets (still one segmented kernel launch, with rows
past the device-upload watermark shipped per batch), ``filtered_graph``
and ``residual`` verify delta IDs host-side.  A compaction
(``VectorMaton.compact``) folds delta + tombstone GC into a fresh
generation and swaps it in with a single reference assignment; plans are
stamped with the generation that compiled them and refuse to execute
against another, so readers that snapshot a runtime keep a consistent
view across the swap.
"""

from __future__ import annotations

import time
import warnings
from collections import Counter
from dataclasses import dataclass, field, replace
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..serve.telemetry import span
from .predicate import CompiledPredicate, CompiledSource

KIND_NONE = -1
KIND_RAW = 0
KIND_GRAPH = 1

_EMPTY_F = np.empty(0, np.float32)
_EMPTY_I = np.empty(0, np.int64)


def ranges(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """The positions [s, s + l) of every (start, length) pair, end to end:
    where CSR segments lie in their flat array."""
    lens = np.asarray(lens, dtype=np.int64)
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    offsets = np.cumsum(lens) - lens
    return (np.repeat(np.asarray(starts, dtype=np.int64) - offsets, lens)
            + np.arange(total, dtype=np.int64))


def chain_layout(inherit: np.ndarray, depth: np.ndarray) -> np.ndarray:
    """States in an order where every inheritance chain is a few
    contiguous runs: the heavy-path decomposition of the forest that
    ``inherit`` (child -> parent, -1 at a root) spans, each heavy path
    laid out child before parent.  A chain walk leaves a heavy path only
    over a light edge, which at least doubles the subtree it enters, so
    a chain crosses at most log2(n) + 1 runs.  ``depth`` must grow from
    child to parent (an automaton state's ``maxlen``: a transition
    appends a symbol); every pass is one array operation per depth."""
    n = len(inherit)
    order = np.argsort(depth, kind="stable")
    cuts = np.flatnonzero(np.diff(depth[order])) + 1
    levels = np.split(order, cuts)
    size = np.ones(n, dtype=np.int64)
    for nodes in levels:                    # children before parents
        par = inherit[nodes]
        has = par >= 0
        np.add.at(size, par[has], size[nodes[has]])
    child = np.flatnonzero(inherit >= 0)
    heavy = np.zeros(n, dtype=bool)
    if len(child):
        by = child[np.lexsort((child, -size[child], inherit[child]))]
        first = np.r_[True, inherit[by[1:]] != inherit[by[:-1]]]
        heavy[by[first]] = True
    head = np.arange(n, dtype=np.int64)
    for nodes in reversed(levels):          # parents before children
        hv = nodes[heavy[nodes]]
        head[hv] = head[inherit[hv]]
    return np.lexsort((depth, head))


def table_order(base_lo: np.ndarray, base_hi: np.ndarray,
                base_ids: np.ndarray, n_rows: int) -> np.ndarray:
    """The resident table's row order (DESIGN.md §2): position p holds
    row ``order[p]``.  Each row goes to the smallest CSR segment that
    holds it, ties to the earlier in CSR (``chain_layout``) order; the
    segments lie in CSR order, with their rows in id order; rows no
    segment holds go last, in id order.  A segment whose rows all went
    to it, as every label's segment does where each row carries one
    label, is then one run of consecutive positions."""
    lens = np.asarray(base_hi, np.int64) - base_lo
    segs = np.flatnonzero(lens)
    span = int(base_hi.max()) + 1 if len(segs) else 1
    none = np.iinfo(np.int64).max
    best = np.full(n_rows, none, np.int64)       # min (length, start) key
    np.minimum.at(best, base_ids[ranges(base_lo[segs], lens[segs])],
                  np.repeat(lens[segs] * span + base_lo[segs], lens[segs]))
    return np.argsort(np.where(best < none, best % span, span),
                      kind="stable")


class VectorStore:
    """Append-only (n, d) float32 table with capacity-doubling growth.

    Replaces the O(N)-copy-per-insert ``np.concatenate`` write path: an
    append is an O(d) row write, and the backing buffer reallocates only
    O(log n) times, so total copy traffic is bounded by ~2× the final
    table size (``bytes_copied`` tracks it; bench_churn asserts the
    bound).  ``view`` is the live (n, d) prefix — a zero-copy slice that
    must be re-fetched after an append, because a reallocation moves the
    data to a new buffer.
    """

    def __init__(self, vectors: np.ndarray, min_capacity: int = 64) -> None:
        v = np.ascontiguousarray(vectors, dtype=np.float32)
        if v.ndim != 2:
            raise ValueError("VectorStore expects an (n, d) table")
        self.n = len(v)
        cap = max(min_capacity, self.n)
        self._buf = np.empty((cap, v.shape[1]), dtype=np.float32)
        self._buf[:self.n] = v
        self.reallocations = 0
        self.bytes_copied = int(v.nbytes)

    @property
    def view(self) -> np.ndarray:
        return self._buf[:self.n]

    def append(self, row: np.ndarray) -> int:
        row = np.asarray(row, dtype=np.float32)
        if row.shape != (self._buf.shape[1],):
            raise ValueError(
                f"expected a ({self._buf.shape[1]},) vector, got shape "
                f"{row.shape} (a scalar or mis-shaped row would silently "
                "broadcast into a corrupt table row)")
        if self.n == len(self._buf):
            grown = np.empty((2 * len(self._buf), self._buf.shape[1]),
                             dtype=np.float32)
            grown[:self.n] = self._buf[:self.n]
            self._buf = grown
            self.reallocations += 1
            self.bytes_copied += int(self.n * self._buf.shape[1] * 4)
        self._buf[self.n] = row
        self.n += 1
        return self.n - 1


class DeltaRuntime:
    """Append-only insert log layered over one frozen generation.

    Exactness argument (DESIGN.md §4): for a freeze-time state u the
    frozen chain cover is exactly V_u at freeze time (Lemma 4), and V
    sets only ever *append* post-freeze ids, so
    ``V_u(now) = frozen cover ∪ chain-delta(u)`` where chain-delta is
    the union of ``state_delta`` lists along u's frozen inheritance
    chain (the affected-state logic in ``VectorMaton.insert`` lands each
    new id at exactly one chain state, mirroring the cover's
    disjointness).  States created after the freeze carry no frozen
    cover and are answered from their live ESAM V set, which the
    predicate compiler reads directly.  Tombstones are subtracted at
    execute time, so every strategy is exact over
    base ∪ delta − tombstones.
    """

    def __init__(self, n_base: int, n_states: int) -> None:
        self.n_base = n_base        # vector-count watermark at freeze
        self.n_states = n_states    # state-count watermark at freeze
        self.version = 0            # bumped per insert (pred-cache key)
        self.pending = 0            # inserts folded by the next compaction
        self.state_delta: Dict[int, List[int]] = {}
        # post-freeze ids in arrival order: the replication delta log is
        # extracted from this (extract_delta_records, DESIGN.md §10) —
        # state_delta scatters ids per chain state, which loses the write
        # order a follower must replay
        self.inserted: List[int] = []
        # graphs born after the freeze — raw→graph promotions and HNSW
        # indexes built for post-freeze clone states.  They are invisible
        # to the frozen generation (not in graph_objs), so delete() must
        # fan tombstones into them directly, and their existence triggers
        # a compaction so the next generation actually searches them.
        self.fresh_graph_states: set = set()

    @property
    def empty(self) -> bool:
        return self.pending == 0

    def record(self, state: int, vector_id: int) -> None:
        """Log that ``state``'s base set gained ``vector_id``.  Called
        from the insert path's affected-state logic; post-freeze states
        are served from the live ESAM and are not recorded."""
        if state < self.n_states:
            self.state_delta.setdefault(state, []).append(vector_id)


def extract_delta_records(vm) -> List[Dict]:
    """Reify the live delta of ``vm``'s current generation as ordered
    replication payloads (DESIGN.md §10).

    One ``{'op': 'insert', ...}`` record per post-freeze id — carrying
    the vector row (copied: the growable table may reallocate under the
    caller), the sequence, and the attributes, in arrival order from
    ``DeltaRuntime.inserted`` — followed by one ``{'op': 'delete', ...}``
    per live tombstone (delete marks are idempotent, so replaying the
    full set is exact even when some predate the freeze).

    The write leader uses this to seed a replica-set delta log when
    replication attaches to an index that already carries unfolded
    writes: a follower bootstrapped from the attach-time checkpoint acks
    the seeded watermark, and a later rejoiner restoring an older
    checkpoint replays these records like any shipped batch.
    """
    rt = vm.runtime
    out: List[Dict] = []
    vectors = vm.vectors
    for i in rt.delta.inserted:
        out.append({
            "op": "insert", "vector_id": int(i),
            "vector": np.array(vectors[i]),
            "sequence": vm.sequences[i],
            "attributes": (dict(vm.attributes[i])
                           if i < len(vm.attributes) else {}),
        })
    for vid in sorted(vm.deleted):
        out.append({"op": "delete", "vector_id": int(vid)})
    return out


@dataclass
class ChainCover:
    """A state's inheritance-chain cover in CSR coordinates (== V_state).

    ``states`` is aligned with ``segments``: the chain state that owns each
    segment.  The sharded executor resolves covers against a *shard-local*
    CSR, whose per-state pointers are keyed by state id — the global
    ``(lo, hi)`` ranges are meaningless there, so the states ride along."""
    segments: List[Tuple[int, int]]
    raw_segments: List[Tuple[int, int]]
    graph_states: List[int]
    size: int
    states: List[int] = field(default_factory=list)


@dataclass
class PlanEntry:
    """Execution plan for one compiled predicate (≥ 1 coalesced requests)."""
    key: object                              # predicate coalescing key
    requests: List[int]                      # request positions in the batch
    sources: List[CompiledSource]            # OR-disjuncts to execute+merge
    est: int = 0                             # estimated |qualified set|

    @property
    def state(self) -> int:
        """Anchor state when the entry is a plain CONTAINS chain; -1 for
        boolean predicates (kept for introspection/tests)."""
        if len(self.sources) == 1 and self.sources[0].strategy == "chain":
            return self.sources[0].anchor
        return -1


@dataclass
class QueryPlan:
    n_requests: int
    entries: List[PlanEntry]
    misses: List[int]                        # requests provably empty
    generation: int = 0                      # runtime that compiled the plan
    delta_version: int = 0                   # delta watermark at compile time

    @property
    def coalesced(self) -> int:
        """Requests answered by a shared plan entry."""
        return sum(len(e.requests) - 1 for e in self.entries)

    @property
    def strategies(self) -> Counter:
        """source strategy -> count, over all entries (bench/debug)."""
        return Counter(s.strategy for e in self.entries for s in e.sources)


@dataclass
class PendingExecution:
    """In-flight result of ``PackedRuntime.dispatch`` (DESIGN.md §7).

    Holds everything ``fetch`` needs to assemble the final per-request
    results: the device launch outputs (still device arrays — JAX's async
    dispatch means the kernels may still be running), the per-request
    (launch, row) routing, host-computed parts (residual verification),
    and — when the device merge ran — the merged ``(R, k)`` device
    arrays.  Between ``dispatch`` and ``fetch`` the host is free to plan
    and dispatch the NEXT wave; touching ``fetch`` is the only point
    that blocks on the device.
    """
    plan: QueryPlan
    k: int
    out: List[Tuple[np.ndarray, np.ndarray]]
    launches: List[Tuple[object, object]]
    dev_parts: List[List[Tuple[int, int]]]
    parts: List[List[Tuple[np.ndarray, np.ndarray]]]
    dev_only: List[int] = field(default_factory=list)
    # residual sources ranked on device: (entry, source, live candidate
    # ids, device (distances, positions)), verified at fetch
    residuals: List[Tuple[object, object, np.ndarray, object]] = field(
        default_factory=list)
    merged: Optional[Tuple[object, object]] = None   # (md, mi) on device
    fetched: bool = False
    wave: int = -1                      # the pipeline's wave id (spans)
    queries: Optional[np.ndarray] = None   # (n_requests, d) float32


class ScanBatch(NamedTuple):
    """A wave's scan items flattened for one descriptor launch
    (``PackedRuntime._assemble_scan_batch``): query rows and their
    owners, the descriptors as CSR ``(starts, lens, owners)``, the
    resident tail (table positions) and the shipped tail (ids, rows) with
    their owners; ``runs``, the table position of each descriptor's first
    row where every descriptor is one run and there is no tail (else
    None)."""
    q_rows: List[int]
    q_owner: np.ndarray
    starts: np.ndarray
    lens: np.ndarray
    owners: np.ndarray
    tres: np.ndarray
    tres_owners: np.ndarray
    tship: np.ndarray
    tship_owners: np.ndarray
    rows: np.ndarray
    runs: Optional[np.ndarray]


def merge_sel(pools: List[List[int]], pad: int,
              rows: Optional[int] = None) -> np.ndarray:
    """The device merge's (R_pad, S) index matrix: row j lists the launch
    rows of request j's candidate pool (``pools[j]``, in merge order) and
    points its other slots at row ``pad``, the merge's all-padding row;
    both sides are bucketed so waves share the merge's executables.
    ``rows`` fixes R_pad where the caller knows a bound that holds for
    every wave."""
    from ..kernels.ops import bucket
    sel = np.full((rows or bucket(len(pools), 8),
                   bucket(max(len(p) for p in pools), 1)), pad, np.int32)
    for j, pool in enumerate(pools):
        sel[j, :len(pool)] = pool
    return sel


class PackedRuntime:
    """Flattened, device-residable view of a built VectorMaton index."""

    def __init__(self, vectors: np.ndarray, kind: np.ndarray,
                 inherit: np.ndarray, base_lo: np.ndarray,
                 base_hi: np.ndarray, base_ids: np.ndarray, graphs: Dict[int, Dict[str, np.ndarray]],
                 graph_objs: Dict[int, object], *, metric: str = "l2",
                 backend: str = "numpy", deleted: Optional[set] = None,
                 sequences: Optional[Sequence] = None,
                 quantize: str = "none", accum: str = "f32",
                 generation: int = 0):
        self.vectors = vectors          # live view; base rows are immutable
        self.kind = kind
        self.inherit = inherit
        self.base_lo = base_lo
        self.base_hi = base_hi
        self.base_ids = base_ids
        self.graphs = graphs            # state -> HNSW.pack() arrays
        self.graph_objs = graph_objs    # state -> host HNSW (host beam search)
        self.metric = metric
        self.backend = backend
        self.deleted = deleted if deleted is not None else set()
        self.sequences = list(sequences) if sequences is not None else []
        self.quantize = quantize
        self.accum = accum
        self.generation = generation
        self.n_states = len(kind)       # state-count watermark at freeze
        # CSR segment count: automaton states + attribute pseudo-segments
        # appended by ``build`` (per-attribute sorted-ID arrays).  Only
        # [0, n_states) are automaton states (kind/inherit/delta apply);
        # [n_states, n_csr) are attribute segments addressed by
        # attr_num/attr_tag and resolved as descriptors like any other.
        self.n_csr = len(base_lo)
        self.attr_schema: Dict[str, str] = {}
        self.attr_num: Dict[str, Tuple[int, np.ndarray]] = {}
        self.attr_tag: Dict[str, Dict[str, int]] = {}
        self.attributes: List[dict] = []   # live view, same as sequences
        self.delta = DeltaRuntime(len(vectors), len(kind))
        # the resident table's position space (DESIGN.md §2): ``perm``
        # maps a position to its row id, ``_pos`` a row id to its
        # position, for the rows at build; rows past them keep
        # position = id.  ``_run_breaks[i]`` counts the CSR entries up to
        # i whose position does not follow the previous entry's, so a
        # descriptor is one run iff it counts none (no chains only: a
        # runtime with chains keeps every launch in the gather form)
        self.perm = table_order(base_lo, base_hi, base_ids, len(vectors))
        self._pos = np.empty_like(self.perm)
        self._pos[self.perm] = np.arange(len(self.perm))
        self._run_breaks: Optional[np.ndarray] = None
        # id -> graph states whose node set contains it (delete fan-out)
        self._id_graph_states: Optional[Dict[int, List[int]]] = None
        self._dev: Optional[dict] = None    # device cache, built once
        self._chains: Optional[bool] = None  # see ``chains``
        if not self.chains and len(base_ids):
            steps = np.diff(self._pos[base_ids]) != 1
            self._run_breaks = np.concatenate(
                [[0], np.cumsum(steps)]).astype(np.int32)
        self._dev_n = 0                     # vector count at upload time
        # predicate key -> (delta version at compile, compiled predicate,
        # planner-measured winning strategy at compile — a later measured
        # winner invalidates the entry so the re-compile replays it)
        self._pred_cache: Dict[
            str, Tuple[int, CompiledPredicate, Optional[str]]] = {}
        # owning index's AdaptivePlanner (set by build; None for bare
        # runtimes).  Executors report (strategy, units, ms) through it;
        # the fold happens at wave heads only (DESIGN.md §11).
        self.planner = None
        # device-resident execution (DESIGN.md §3).  The three toggles are
        # parity escape hatches: each False routes that stage through the
        # legacy host-mediated path, which tests/test_device_exec.py uses
        # as the bit-exactness oracle for the device-resident path.
        self.use_descriptors = True     # CSR descriptors vs host id upload
        self.fuse_graphs = True         # bucket-fused vs per-state beams
        self.device_merge = True        # device vs host per-request merge
        self.shard_descriptors = True   # sharded CSR descriptors vs the
                                        # legacy per-entry dense-mask path
        # (mesh, axis, watermark) -> ShardedDeviceIndex (DESIGN.md §5);
        # _shard_auto records the watermark frozen by the first n=None use
        # per (mesh, axis), so auto and explicit callers share a residency
        self._shard_dev: Dict = {}
        self._shard_auto: Dict = {}
        # host→device traffic accounting, per batch class (bench gate)
        self.traffic: Dict[str, int] = {
            "batches": 0, "bytes_to_device": 0, "candidate_id_bytes": 0,
            "query_bytes": 0, "descriptor_bytes": 0, "row_bytes": 0,
            "mask_bytes": 0, "shard_batches": 0, "shard_mask_bytes": 0,
            "shard_descriptor_bytes": 0, "shard_tail_bytes": 0,
            "shard_query_bytes": 0,
            # query-row x candidate-row pairs the scan grids evaluate, and
            # the pairs of real query rows with their own candidates
            "scan_pairs_computed": 0, "scan_pairs_needed": 0,
            # transfers and program dispatches the executor issues for
            # the scan, graph, residual-ranking and merge launches and the
            # fetch
            "host_device_calls": 0,
            # (start, length, owner) descriptors the scan launches ship
            "scan_descriptors": 0,
            # descriptor launches (fp32 and SQ8), and those of them in
            # the in-place form (``_scan_fp32``)
            "scan_launches": 0, "scan_inplace_launches": 0}
        # residual verification: candidate rows checked against their
        # predicate on the host (time in wave_times residual_verify_ms)
        self.residual_stats: Dict[str, int] = {"rows_verified": 0}
        # SQ8 scan-path accounting: every batch is either certified
        # (provably equal to the fp32 scan) or escalated to it; fallbacks
        # count batches the eligibility gate routed to fp32 outright
        self.sq8_stats: Dict[str, int] = {
            "batches": 0, "certified": 0, "escalations": 0, "fallbacks": 0}
        self._sq8_warned = False
        # adaptive escalation policy: a workload whose candidate sets are
        # too dense for the worst-case certificate (big n, tight
        # neighbour gaps) would pay int8 scan + rerank + fp32 scan every
        # batch; after this many CONSECUTIVE escalations the runtime
        # flips to the fp32 scan outright (counted as fallbacks), so the
        # sq8 default is never asymptotically slower than fp32.  A
        # certified batch resets the streak.  ``sq8_escalate=False``
        # trusts the rerank output without the certificate sync — the
        # approximate operating point the frontier benchmark measures.
        self.sq8_escalate = True
        # consecutive escalations: one streak (key None) for the
        # launches of plain buckets, one per shape (bucket key) for
        # floored launches, whose shapes hold candidate sets of unlike
        # density (``_execute_scan_sq8``)
        self._sq8_bad_streak: Dict[Optional[tuple], int] = {}
        self.SQ8_MAX_STREAK = 3
        # floored shapes whose fp32 program the SQ8 path has run once
        self._sq8_fp32_warm: set = set()
        # cumulative per-wave wall-clock (ms), surfaced by
        # maintenance_stats as time_*_ms.  Device dispatch is async, so
        # launch_ms and merge_launch_ms are trace+dispatch cost; the
        # device wait is fetch_sync_ms alone, merge_ms the host merge.
        self.wave_times: Dict[str, float] = {
            "plan_ms": 0.0, "upload_ms": 0.0, "launch_ms": 0.0,
            "merge_launch_ms": 0.0, "fetch_sync_ms": 0.0, "merge_ms": 0.0,
            "residual_verify_ms": 0.0}

    # ------------------------------------------------------------------ #
    # construction
    # ------------------------------------------------------------------ #

    @classmethod
    def build(cls, vm, generation: int = 0) -> "PackedRuntime":
        """A VectorMaton's chain structure and per-state indexes, as the
        index's ``StateIndexes.flatten`` gives them, laid out in
        ``chain_layout`` order, with the attribute segments appended."""
        n = vm.esam.num_states
        si = vm.state_index
        kind, ptr, ids = si.flatten()
        inherit = np.asarray(vm.inherit, dtype=np.int64)
        layout = chain_layout(inherit, np.asarray(vm.esam.maxlen, np.int64))
        lens = np.diff(ptr)
        base_lo = np.empty(n, dtype=np.int64)
        base_lo[layout] = np.cumsum(lens[layout]) - lens[layout]
        base_hi = base_lo + lens
        graph_objs: Dict[int, object] = dict(si.graphs)
        graphs = {u: g.pack() for u, g in graph_objs.items()}
        chunks: List[np.ndarray] = [ids[ranges(ptr[layout], lens[layout])]]
        # Attribute pseudo-segments (DESIGN.md §9): one sorted-by-value
        # ID segment per numeric field (rank ranges answer Range leaves
        # as descriptor slices) and one sorted-ID segment per (tag field,
        # value).  They live in the same CSR as chain segments, so the
        # resident base_ids answers them with zero candidate-id upload.
        schema = dict(getattr(vm.config, "schema", None) or {})
        attr_rows = getattr(vm, "attributes", None) or []
        attr_num: Dict[str, Tuple[int, np.ndarray]] = {}
        attr_tag: Dict[str, Dict[str, int]] = {}
        attr_segs: List[np.ndarray] = []
        if schema:
            n_rows = min(len(vm.vectors), len(attr_rows))

            def _pseudo(seg: np.ndarray) -> int:
                attr_segs.append(np.asarray(seg, dtype=np.int64))
                return n + len(attr_segs) - 1

            for f in sorted(schema):
                if schema[f] == "numeric":
                    ids = np.asarray([i for i in range(n_rows)
                                      if f in attr_rows[i]], np.int64)
                    vals = np.asarray([float(attr_rows[int(i)][f])
                                       for i in ids], np.float64)
                    order = np.lexsort((ids, vals))
                    attr_num[f] = (_pseudo(ids[order]), vals[order])
                else:
                    groups: Dict[str, List[int]] = {}
                    for i in range(n_rows):
                        v = attr_rows[i].get(f)
                        if v is not None:
                            groups.setdefault(str(v), []).append(i)
                    attr_tag[f] = {
                        v: _pseudo(np.asarray(groups[v], np.int64))
                        for v in sorted(groups)}
        if attr_segs:
            alens = np.asarray([len(s) for s in attr_segs], np.int64)
            alo = int(lens.sum()) + np.cumsum(alens) - alens
            base_lo = np.concatenate([base_lo, alo])
            base_hi = np.concatenate([base_hi, alo + alens])
            chunks.extend(attr_segs)
        base_ids = (np.concatenate(chunks) if len(chunks) > 1
                    else chunks[0])
        rt = cls(vm.vectors, kind, inherit, base_lo, base_hi, base_ids,
                 graphs, graph_objs,
                 metric=vm.config.metric, backend=vm.config.backend,
                 deleted=vm.deleted,
                 quantize=getattr(vm.config, "quantize", "none"),
                 accum=getattr(vm.config, "accum", "f32"),
                 generation=generation)
        # share (don't copy) the live sequence list: residual verification
        # of delta ids must see sequences appended after this freeze
        rt.sequences = getattr(vm, "sequences", rt.sequences)
        rt.attr_schema = schema
        rt.attr_num = attr_num
        rt.attr_tag = attr_tag
        # live view for the same reason as sequences: attribute leaves
        # evaluate post-freeze inserts host-side at compile time
        rt.attributes = getattr(vm, "attributes", rt.attributes)
        # the index-owned planner: feedback outlives this generation
        rt.planner = getattr(vm, "planner", None)
        return rt

    # ------------------------------------------------------------------ #
    # device residency
    # ------------------------------------------------------------------ #

    def table_rows(self, n: int) -> np.ndarray:
        """Row ids of the resident table's first ``n`` positions."""
        m = len(self.perm)
        return (self.perm[:n] if n <= m
                else np.concatenate([self.perm, np.arange(m, n)]))

    def positions(self, ids) -> np.ndarray:
        """Table positions of row ids (int64; ids past the build's rows
        keep their value)."""
        ids = np.asarray(ids, np.int64)
        out = ids.copy()
        m = ids < len(self._pos)
        out[m] = self._pos[ids[m]]
        return out

    def row_ids(self, pos) -> np.ndarray:
        """Row ids of table positions, -1 kept (int64)."""
        pos = np.asarray(pos, np.int64)
        out = pos.copy()
        m = (pos >= 0) & (pos < len(self.perm))
        out[m] = self.perm[pos[m]]
        return out

    def to_device(self) -> dict:
        """Upload the packed arrays once; reused by every later batch.
        ``_dev_n`` records the row count at upload time — delta rows
        appended later are shipped per batch by the executor's
        watermark-split gather, never by re-uploading the table.

        The table uploads in position space (``table_order``): row
        ``table_rows(n)[p]`` at position p, and every device array that
        points into it — ``base_ids``, the ``deleted`` mask, the graph
        ids — holds positions.  The executor converts ids to positions
        where it ships them and the answers back to ids at ``fetch``.

        Graph matrices upload twice over: per state (legacy per-graph
        path, parity oracle) and as size-bucketed ``(G, n_max, 2M)``
        stacks (``graph_buckets``) that the fused executor vmaps one beam
        launch over per bucket.  ``graph_slot`` maps a state to its
        (bucket key, stack row).  Stack padding: ids 0 / neighbours -1 —
        padded slots are unreachable (no entry point or edge leads to
        them), asserted by the fused-vs-per-graph parity test."""
        if self._dev is None:
            import jax
            import jax.numpy as jnp

            from ..kernels import ops
            self._dev_n = len(self.vectors)
            dmask = np.zeros(self._dev_n, dtype=bool)
            if self.deleted:
                gone = [i for i in self.deleted if i < self._dev_n]
                dmask[self.positions(gone)] = True
            by_bucket: Dict[Tuple[int, int], List[int]] = {}
            for u, pk in self.graphs.items():
                bkey = (ops.bucket(len(pk["ids"]), 8),
                        pk["level0"].shape[1])
                by_bucket.setdefault(bkey, []).append(u)
            buckets: Dict[Tuple[int, int], dict] = {}
            slots: Dict[int, Tuple[Tuple[int, int], int]] = {}
            for bkey, states in by_bucket.items():
                n_pad, width = bkey
                g = len(states)
                ids = np.zeros((g, n_pad), np.int32)
                lvl = np.full((g, n_pad, width), -1, np.int32)
                ent = np.zeros(g, np.int32)
                for j, u in enumerate(states):
                    pk = self.graphs[u]
                    ids[j, :len(pk["ids"])] = self.positions(pk["ids"])
                    lvl[j, :len(pk["level0"])] = pk["level0"]
                    ent[j] = pk["entry"][0]
                    slots[u] = (bkey, j)
                buckets[bkey] = {
                    "ids": jax.device_put(jnp.asarray(ids)),
                    "level0": jax.device_put(jnp.asarray(lvl)),
                    "entry": jax.device_put(jnp.asarray(ent)),
                }
            rows = np.asarray(self.vectors)
            self._dev = {
                "vectors": jax.device_put(
                    rows[self.table_rows(self._dev_n)]),
                "base_ids": jax.device_put(
                    self.positions(self.base_ids).astype(np.int32)),
                "deleted": jax.device_put(jnp.asarray(dmask)),
                "graphs": {
                    u: {"ids": jax.device_put(jnp.asarray(
                            self.positions(pk["ids"]))),
                        "level0": jax.device_put(jnp.asarray(pk["level0"])),
                        "entry": jax.device_put(jnp.asarray(pk["entry"][0]))}
                    for u, pk in self.graphs.items()},
                "graph_buckets": buckets,
                "graph_slot": slots,
            }
        if self.quantize == "sq8" and "quant" not in self._dev:
            # resident int8 table: codes + per-row (scale, sqnorm,
            # code-L1) — the SQ8 scan reads these instead of the fp32
            # rows; derived on device from the already-resident table so
            # nothing extra ships from the host.  Outside the ``if`` so
            # a runtime toggled to sq8 after its first upload (bench
            # strategy sweeps) still gets the table.
            import jax
            import jax.numpy as jnp

            from ..kernels.quant import quantize_sq8_ext
            if self._dev_n:
                self._dev["quant"] = tuple(
                    jax.device_put(a)
                    for a in quantize_sq8_ext(self._dev["vectors"]))
            else:
                d = (int(self.vectors.shape[1])
                     if self.vectors.ndim == 2 else 0)
                self._dev["quant"] = (
                    jnp.empty((0, d), jnp.int8),
                    jnp.empty((0, 1), jnp.float32),
                    jnp.empty((0, 1), jnp.float32),
                    jnp.empty((0, 1), jnp.float32))
        return self._dev

    _SHARD_DEV_MAX = 4

    def to_device_sharded(self, mesh, axis: str = "data",
                          n: Optional[int] = None):
        """Row-sharded device residency over ``mesh`` (DESIGN.md §5):
        vector table, tombstone bitmap, and the shard-local CSR, uploaded
        once per (mesh, axis, watermark) and reused by every later
        sharded batch.  ``n`` pins the shard watermark (rows past it are
        host-merged delta overflow); ``None`` freezes the current table
        length on first use.  The cache is a small LRU: each residency
        pins a full padded device copy of the table, so a caller that
        keeps moving the watermark recycles slots instead of accumulating
        table copies until the next compaction."""
        from ..distributed.sharded_search import ShardedDeviceIndex
        if n is None:
            n = self._shard_auto.get((mesh, axis))
            if n is None:
                n = len(self.vectors)
                self._shard_auto[(mesh, axis)] = n
        key = (mesh, axis, int(n))
        sh = self._shard_dev.pop(key, None)
        if sh is None:
            while len(self._shard_dev) >= self._SHARD_DEV_MAX:
                self._shard_dev.pop(next(iter(self._shard_dev)))
            sh = ShardedDeviceIndex(self, mesh, axis=axis, n=n)
        self._shard_dev[key] = sh                # (re)insert: LRU refresh
        return sh

    def mark_deleted(self, vector_id: int) -> None:
        """Keep the device-side tombstone mask in sync (no re-upload of
        the index arrays — a single scatter into the resident mask).
        Delta ids past the upload watermark are filtered host-side when
        their candidate lists are built.  Sharded residencies sync lazily
        instead — one batched scatter at the head of each sharded batch
        (``ShardedDeviceIndex.sync_tombstones``), not one per delete."""
        if self._dev is not None and vector_id < self._dev_n:
            self._dev["deleted"] = self._dev["deleted"].at[
                int(self.positions(vector_id))].set(True)

    def graph_states_of(self, vector_id: int) -> List[int]:
        """Graph states whose node set contains ``vector_id``.  Built from
        the live host graph objects (not the frozen CSR) so ids added to
        a graph after this generation froze still fan tombstones out;
        the insert path invalidates the cache when it grows a graph."""
        if self._id_graph_states is None:
            m: Dict[int, List[int]] = {}
            for u, g in self.graph_objs.items():
                for gid in g.ids:
                    m.setdefault(int(gid), []).append(u)
            self._id_graph_states = m
        return self._id_graph_states.get(int(vector_id), [])

    # ------------------------------------------------------------------ #
    # planner (host)
    # ------------------------------------------------------------------ #

    def plan(self, compiled: Sequence[CompiledPredicate]) -> QueryPlan:
        """Coalesce a batch of compiled predicates into plan entries.
        Requests whose predicates share a canonical key share one entry;
        provably-empty predicates (pattern ∉ corpus) are misses."""
        entries: Dict[object, PlanEntry] = {}
        misses: List[int] = []
        for r, cp in enumerate(compiled):
            if cp.empty:
                misses.append(r)
                continue
            e = entries.get(cp.key)
            if e is None:
                e = PlanEntry(cp.key, [], cp.sources, cp.est)
                entries[cp.key] = e
            e.requests.append(r)
        return QueryPlan(len(compiled), list(entries.values()), misses,
                         generation=self.generation,
                         delta_version=self.delta.version)

    @property
    def chains(self) -> bool:
        """Whether a state other than the root inherits, so that a
        CONTAINS can resolve to a chain of several segments: rows carry
        sequences of their own, not one label each.  Every descriptor
        launch of such a runtime takes the shape floors
        (``ops.floored``)."""
        if self._chains is None:
            self._chains = bool(np.any(self.inherit[1:] >= 0))
        return self._chains

    def chain_cover(self, state: int) -> ChainCover:
        """Walk the inheritance chain; CSR ranges covering exactly V_state,
        one per state in ``segments``, and in ``raw_segments`` the raw
        states' ranges with each contiguous run (``chain_layout``) joined
        into one: the scan's descriptors."""
        segments: List[Tuple[int, int]] = []
        raw_segments: List[Tuple[int, int]] = []
        graph_states: List[int] = []
        states: List[int] = []
        size = 0
        u = state
        while u != -1:
            lo, hi = int(self.base_lo[u]), int(self.base_hi[u])
            if hi > lo:
                segments.append((lo, hi))
                states.append(u)
                size += hi - lo
                if self.kind[u] != KIND_RAW:
                    graph_states.append(u)
                elif raw_segments and raw_segments[-1][1] == lo:
                    raw_segments[-1] = (raw_segments[-1][0], hi)
                else:
                    raw_segments.append((lo, hi))
            u = int(self.inherit[u])
        return ChainCover(segments, raw_segments, graph_states, size,
                          states=states)

    def chain_delta_ids(self, state: int) -> np.ndarray:
        """New ids in V_state since this generation froze, sorted.  Walks
        the frozen inheritance chain: the insert path records each new id
        at exactly one chain state (the deepest whose V gained it), so
        the union along the chain is disjoint and, together with the
        frozen cover, reproduces the live V_state exactly."""
        sd = self.delta.state_delta
        if not sd:
            return _EMPTY_I
        out: List[int] = []
        u = state
        while u != -1:
            out.extend(sd.get(u, ()))
            u = int(self.inherit[u])
        if not out:
            return _EMPTY_I
        return np.sort(np.asarray(out, dtype=np.int64))

    def entry_mask(self, entry: PlanEntry) -> np.ndarray:
        """Exact (n,) bool membership of the entry's qualified set — OR over
        sources, residual verification applied.  Feeds the distributed
        path's per-entry validity mask and the test oracles."""
        n = len(self.vectors)
        m = np.zeros(n, dtype=bool)
        for s in entry.sources:
            sm = np.zeros(n, dtype=bool)
            if s.strategy in ("chain", "filtered_graph"):
                for lo, hi in s.segments:
                    sm[self.base_ids[lo:hi]] = True
                if s.delta_ids is not None:
                    sm[s.delta_ids] = True
                if s.allowed is not None:
                    a = s.allowed
                    if len(a) < n:
                        a = np.pad(a, (0, n - len(a)))
                    sm &= a[:n]
            else:
                sm[s.ids] = True
                if s.delta_ids is not None:
                    sm[s.delta_ids] = True
            if s.verify is not None:
                for i in np.nonzero(sm)[0]:
                    if not s.verify.matches(self.sequences[int(i)],
                                            self._attrs_of(int(i))):
                        sm[i] = False
            m |= sm
        return m

    def _attrs_of(self, gid: int) -> Optional[dict]:
        """Record attributes for residual verification; None when the
        collection carries no attributes (pattern-only predicates never
        read them)."""
        a = self.attributes
        return a[gid] if a and gid < len(a) else None

    # ------------------------------------------------------------------ #
    # executor
    # ------------------------------------------------------------------ #

    def execute(self, queries: np.ndarray, plan: QueryPlan, k: int,
                ef_search: int = 64
                ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Answer every request in the plan; returns [(dists, ids)] aligned
        with the request batch.

        Device (jax) backend — the warm path touches the host only for
        planning integers and the final (k,) results (DESIGN.md §3):

          * ONE descriptor-driven segmented kernel launch for every
            brute-forced candidate set (frozen chain covers resolve
            against the resident CSR on device; only delta tails past the
            upload watermark ship per batch);
          * ONE fused beam launch per graph size bucket, vmapped over
            (graph, query) pairs — not one per state — with the tombstone
            over-fetch clamped at the beam's ef-list capacity (past it
            the resident deleted bitmap filters in-loop instead);
          * ONE device-side merge (segmented dedup + top-k fold) for all
            requests whose parts are device launch rows; requests with
            host-side parts (``residual`` verification) merge on host.

        Host (numpy) backend: same plan, NumPy kernels, host merge — the
        bit-exactness oracle for every device stage."""
        return self.fetch(self.dispatch(queries, plan, k,
                                        ef_search=ef_search))

    def dispatch(self, queries: np.ndarray, plan: QueryPlan, k: int,
                 ef_search: int = 64, wave: int = -1) -> PendingExecution:
        """Launch every device stage of the plan WITHOUT syncing on the
        results (DESIGN.md §7): staleness checks, the segmented scan
        launch, the fused beam launches, residual verification (host
        work), and the device-side merge fold are all dispatched — JAX's
        async dispatch returns device futures — and the per-request
        assembly integers are packed into a ``PendingExecution``.  The
        caller overlaps the next wave's planning/dispatch with this
        wave's device execution and calls ``fetch`` when it needs the
        results.  ``execute`` is the synchronous composition."""
        if plan.generation != self.generation:
            raise ValueError(
                f"stale plan: compiled against generation "
                f"{plan.generation}, executing on generation "
                f"{self.generation} — snapshot the runtime once per batch "
                "(VectorMaton.snapshot) so a compaction swap cannot split "
                "plan and execute across generations")
        if plan.delta_version != self.delta.version:
            raise ValueError(
                f"stale plan: compiled at delta version "
                f"{plan.delta_version}, executing at "
                f"{self.delta.version} — an insert landed between plan "
                "and execute, so the plan's delta id lists are "
                "incomplete; re-plan (query_batch does this per batch)")
        queries = np.ascontiguousarray(queries, dtype=np.float32)
        out: List[Tuple[np.ndarray, np.ndarray]] = [
            (_EMPTY_F, _EMPTY_I)] * plan.n_requests
        parts: List[List[Tuple[np.ndarray, np.ndarray]]] = [
            [] for _ in range(plan.n_requests)]
        launches: List[Tuple[object, object]] = []   # (vals, gids) on device
        dev_parts: List[List[Tuple[int, int]]] = [
            [] for _ in range(plan.n_requests)]      # (launch idx, row)
        pending = PendingExecution(plan=plan, k=k, out=out,
                                   launches=launches, dev_parts=dev_parts,
                                   parts=parts, wave=wave, queries=queries)
        if not plan.entries:
            pending.fetched = True
            return pending
        scan_items, graph_shared, graph_filtered, residual_items = (
            self._gather_work(plan))
        sel = None
        if self.backend == "jax":
            self.traffic["batches"] += 1
            # a wave of scans alone merges every request on device from
            # the scan's one launch, so the merge's index matrix is known
            # now and rides in the scan's upload
            scan_only = self.device_merge and not (
                graph_shared or graph_filtered or residual_items)
            execute_scan = (self._execute_scan_sq8 if self.quantize == "sq8"
                            else self._execute_scan_device)
            sel = execute_scan(queries, scan_items, k, launches, dev_parts,
                               wave, with_sel=scan_only)
            t0 = time.perf_counter()
            self._execute_graphs_device(queries, graph_shared, graph_filtered,
                                        k, ef_search, launches, dev_parts)
            self.wave_times["launch_ms"] += (time.perf_counter() - t0) * 1e3
        else:
            self._execute_scan_host(queries, scan_items, k, parts)
            self._execute_graphs_host(queries, graph_shared, graph_filtered,
                                      k, ef_search, parts)
        for e, s in residual_items:
            if self.backend == "jax":
                pending.residuals.extend(self._launch_residual(queries, e, s))
            else:
                self._execute_residual(queries, e, s, k, parts)
        # device-merge half that can be DISPATCHED now: requests whose
        # parts are all launch rows fold on device; the (R, k) result
        # stays a device future until fetch
        n = plan.n_requests
        verified = {r for e, *_ in pending.residuals for r in e.requests}
        if launches and self.device_merge:
            pending.dev_only = [r for r in range(n)
                                if dev_parts[r] and not parts[r]
                                and r not in verified]
        if pending.dev_only:
            with span("launch_merge", wave=wave):
                t0 = time.perf_counter()
                pending.merged = self._merge_device_launch(
                    pending.dev_only, launches, dev_parts, k, sel)
                self.wave_times["merge_launch_ms"] += (
                    (time.perf_counter() - t0) * 1e3)
        return pending

    def fetch(self, pending: PendingExecution
              ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Sync on a dispatched wave's device results and assemble the
        final per-request (dists, ids).  This is the ONLY point the
        executor blocks on the device; everything before it is async
        dispatch, so a pipelined caller fetches wave N while wave N+1 is
        already executing."""
        if pending.fetched:
            return pending.out
        if pending.launches or pending.residuals:
            import jax
            with span("sync", wave=pending.wave):
                t0 = time.perf_counter()
                jax.block_until_ready((pending.merged, pending.launches,
                                       [r[3] for r in pending.residuals]))
                self.wave_times["fetch_sync_ms"] += (
                    (time.perf_counter() - t0) * 1e3)
        if pending.residuals:
            with span("verify_residual", wave=pending.wave):
                self._verify_residuals(pending)
        with span("merge_host", wave=pending.wave):
            t0 = time.perf_counter()
            self._merge_fetch(pending)
            self._rescore(pending)
            self.wave_times["merge_ms"] += (time.perf_counter() - t0) * 1e3
        pending.fetched = True
        # a fetched wave pins no device memory: its launch outputs and
        # residual rankings (2 MB for 8 queries over 32K candidates) go
        pending.launches, pending.residuals, pending.merged = [], [], None
        return pending.out

    def _rescore(self, pending: PendingExecution) -> None:
        """Each answer's L2 distances recomputed as sum((x - y)^2) in
        float32, in the order the scan ranked them.  The scan ranks by
        |x|^2 + |y|^2 - 2 x.y, whose float32 sum loses about
        eps * (|x|^2 + |y|^2) / |x - y|^2 to cancellation: 3e-7 of the
        distance at 1024 dimensions, as much as one precision step
        below float32 gives.  A ``bf16`` accumulation keeps its scan's
        distances, which are what it asked for."""
        if self.metric != "l2" or self.accum != "f32":
            return
        out = pending.out
        reqs = [r for r, (_, i) in enumerate(out) if len(i)]
        if not reqs:
            return
        ids = np.concatenate([out[r][1] for r in reqs])
        owner = np.repeat(reqs, [len(out[r][1]) for r in reqs])
        diff = (np.asarray(self.vectors[ids], np.float32)
                - pending.queries[owner])
        dist = np.sum(diff * diff, axis=1)      # pairwise summation
        lo = 0
        for r in reqs:
            hi = lo + len(out[r][1])
            out[r] = (dist[lo:hi], out[r][1])
            lo = hi

    def _merge_fetch(self, pending: PendingExecution) -> None:
        """Per-request merge: dedup ids across OR disjuncts / overlapping
        sources (keep the closest), drop tombstones, cut to k.  Requests
        whose parts are all device launch rows were folded on device at
        dispatch (``merge_topk_device``) — here their (R, k) rows cross
        to the host; the rest — host backend, or residual parts present —
        run the NumPy merge, which is the bit-exactness oracle
        (``device_merge=False`` forces it everywhere)."""
        import jax
        plan, launches, dev_parts, parts, k, out = (
            pending.plan, pending.launches, pending.dev_parts,
            pending.parts, pending.k, pending.out)
        n = plan.n_requests
        dev_only = pending.dev_only
        if pending.merged is not None:
            md, mi = jax.device_get(pending.merged)
            self.traffic["host_device_calls"] += 1
            for j, r in enumerate(dev_only):
                valid = mi[j] >= 0
                out[r] = (md[j][valid], self.row_ids(mi[j][valid]))
        done = set(dev_only)
        conv: List[Optional[Tuple[np.ndarray, np.ndarray]]] = (
            [None] * len(launches))

        def _host_rows(li: int) -> Tuple[np.ndarray, np.ndarray]:
            if conv[li] is None:
                v, g = jax.device_get(launches[li])
                conv[li] = (v, self.row_ids(g))
                self.traffic["host_device_calls"] += 1
            return conv[li]

        for r in range(n):
            if r in done:
                continue
            host_parts = parts[r]
            if dev_parts[r]:
                pre = []
                for li, row in dev_parts[r]:
                    v, g = _host_rows(li)
                    valid = g[row] >= 0
                    pre.append((v[row][valid], g[row][valid]))
                host_parts = pre + host_parts
            if not host_parts:
                continue
            d = np.concatenate([p[0] for p in host_parts])
            i = np.concatenate([p[1] for p in host_parts])
            if self.deleted:
                keep = ~np.isin(i, np.fromiter(self.deleted,
                                               dtype=np.int64))
                d, i = d[keep], i[keep]
            order = np.argsort(d, kind="stable")
            d, i = d[order], i[order]
            # OR disjuncts can overlap: keep the first (closest) per id
            _, first = np.unique(i, return_index=True)
            if len(first) != len(i):
                keep = np.zeros(len(i), dtype=bool)
                keep[first] = True
                d, i = d[keep], i[keep]
            out[r] = (d[:k], i[:k])

    def _merge_device_launch(self, reqs: List[int], launches, dev_parts,
                             k: int, sel=None) -> Tuple[object, object]:
        """Gather each request's launch rows by index matrix and fold
        dedup + top-k on device — replacing the per-request Python
        concatenate/argsort loop with one bucketed launch and ONE (R, k)
        transfer back.  A wave of one launch (every scan-only wave) hands
        that launch's outputs to the merge as they are, with ``sel``
        already uploaded by the scan; several launches (graph beams) are
        stacked into one (T, W) pool first.  Returns the (R_pad, k)
        device arrays WITHOUT syncing: ``fetch`` crosses them to the host
        when the caller needs the results."""
        import jax
        import jax.numpy as jnp

        from ..kernels import ops
        dev = self.to_device()
        calls = 1                                       # the merge launch
        if len(launches) == 1:
            big_d, big_i = launches[0]
            t_pad = int(big_d.shape[0])
            offs = [0]
        else:
            w = max(int(v.shape[1]) for v, _ in launches)
            pd, pi, offs = [], [], []
            t = 0
            for v, g in launches:
                if int(v.shape[1]) < w:
                    v = jnp.pad(v, ((0, 0), (0, w - int(v.shape[1]))),
                                constant_values=np.inf)
                    g = jnp.pad(g, ((0, 0), (0, w - int(g.shape[1]))),
                                constant_values=-1)
                    calls += 2
                pd.append(v)
                pi.append(g)
                offs.append(t)
                t += int(v.shape[0])
            t_pad = ops.bucket(t, 8)
            big_d = jnp.pad(jnp.concatenate(pd, axis=0),
                            ((0, t_pad - t), (0, 0)), constant_values=np.inf)
            big_i = jnp.pad(jnp.concatenate(pi, axis=0),
                            ((0, t_pad - t), (0, 0)), constant_values=-1)
            calls += 4
        if sel is None:
            sel = jax.device_put(merge_sel(
                [[offs[li] + row for li, row in dev_parts[r]] for r in reqs],
                t_pad, t_pad if len(launches) == 1 else None))
            calls += 1
        delmask = (dev["deleted"] if self._dev_n
                   else jnp.zeros(1, dtype=bool))
        md, mi = ops.merge_topk_device(big_d, big_i, sel, delmask, k)
        self.traffic["host_device_calls"] += calls
        r_pad, s_max = sel.shape
        ops.record_launch("merge",
                          (t_pad, s_max, int(big_d.shape[1]), r_pad, k))
        return md, mi

    def _gather_work(self, plan: QueryPlan):
        """Split the plan into the executor's four work classes.

        Scan items are ``(entry, frozen CSR segments, explicit tail
        ids)``: the device executor resolves the segments as descriptors
        against the resident CSR (zero candidate-id upload), the host
        executor materializes both.  Tails hold everything that is not a
        frozen segment — delta inserts, masked conjunction survivors,
        post-freeze state V sets."""
        scan_items: List[Tuple[PlanEntry, List[Tuple[int, int]],
                               np.ndarray]] = []
        graph_shared: Dict[int, List[int]] = {}
        graph_filtered: List[Tuple[int, np.ndarray, List[int]]] = []
        residual_items: List[Tuple[PlanEntry, CompiledSource]] = []
        for e in plan.entries:
            for s in e.sources:
                delta = (s.delta_ids if s.delta_ids is not None
                         and len(s.delta_ids) else None)
                if s.strategy == "chain":
                    tail = delta if delta is not None else _EMPTY_I
                    if s.raw_segments or len(tail):
                        scan_items.append((e, list(s.raw_segments), tail))
                    for u in s.graph_states:
                        graph_shared.setdefault(u, []).extend(e.requests)
                elif s.strategy == "scan":
                    if len(s.ids):
                        scan_items.append((e, [], s.ids))
                elif s.strategy == "filtered_graph":
                    parts = []
                    if s.raw_segments:
                        cand = np.concatenate(
                            [self.base_ids[lo:hi]
                             for lo, hi in s.raw_segments])
                        cand = cand[s.allowed[cand]]
                        if len(cand):
                            parts.append(cand)
                    if delta is not None:     # host-verified at compile time
                        parts.append(delta)
                    if parts:
                        scan_items.append((e, [], np.concatenate(parts)))
                    for u in s.graph_states:
                        graph_filtered.append((u, s.allowed, e.requests))
                elif s.strategy == "residual":
                    residual_items.append((e, s))
                else:  # pragma: no cover - compiler invariant
                    raise ValueError(f"unknown strategy {s.strategy!r}")
        return scan_items, graph_shared, graph_filtered, residual_items

    # ---- brute-forced candidate sets ---------------------------------- #

    def _live(self, cand: np.ndarray) -> np.ndarray:
        if self.deleted:
            cand = cand[~np.isin(
                cand, np.fromiter(self.deleted, dtype=np.int64))]
        return cand

    @staticmethod
    def _scan_units(scan_items) -> int:
        """Cost-model work units for a scan batch: candidate rows ranked,
        summed as |cand| × |requests| per item (DESIGN.md §11)."""
        units = 0
        for e, segs, tail in scan_items:
            cand = sum(hi - lo for lo, hi in segs) + len(tail)
            units += cand * len(e.requests)
        return units

    def _observe(self, strategy: str, units: int, dt_s: float) -> None:
        """Report one executed work item to the owning index's planner
        (no-op for bare runtimes / static mode); folded at wave heads."""
        if self.planner is not None:
            self.planner.observe(strategy, units, dt_s * 1e3)

    def _execute_scan_host(self, queries, scan_items, k, parts) -> None:
        from ..kernels import ops
        t0 = time.perf_counter()
        for e, segs, tail in scan_items:
            chunks = [self.base_ids[lo:hi] for lo, hi in segs]
            if len(tail):
                chunks.append(tail)
            cand = self._live(np.concatenate(chunks))
            if len(cand) == 0:
                continue
            sub = self.vectors[cand]
            d, li = ops.topk_numpy(queries[e.requests], sub,
                                   min(k, len(cand)), metric=self.metric)
            for row, r in enumerate(e.requests):
                valid = li[row] >= 0
                parts[r].append((d[row][valid], cand[li[row][valid]]))
        self._observe("scan", self._scan_units(scan_items),
                      time.perf_counter() - t0)

    def _assemble_scan_batch(self, queries, scan_items
                             ) -> Optional[ScanBatch]:
        """Flatten the batch's scan items into one descriptor launch:
        frozen CSR segments become ``(start, len, owner)`` triples; tails
        split at the upload watermark into resident ids (device-gathered,
        device-tombstoned, shipped as table positions) and shipped ids
        (+ their rows — only the post-watermark delta ever ships).
        ``use_descriptors=False`` demotes every segment to explicit ids
        (the legacy candidate-upload path, kept as the parity oracle)."""
        from ..kernels import ops
        if not scan_items:
            return None
        self.to_device()
        dn = self._dev_n
        q_rows: List[int] = []
        q_owner: List[int] = []
        dstarts: List[int] = []
        dlens: List[int] = []
        downers: List[int] = []
        tres: List[np.ndarray] = []
        tres_o: List[np.ndarray] = []
        tship: List[np.ndarray] = []
        tship_o: List[np.ndarray] = []
        id_bytes = 0
        for owner, (e, segs, tail) in enumerate(scan_items):
            if not self.use_descriptors and segs:
                chunks = [self.base_ids[lo:hi] for lo, hi in segs]
                if len(tail):
                    chunks.append(tail)
                tail = np.concatenate(chunks)
                segs = []
            for lo, hi in segs:
                dstarts.append(lo)
                dlens.append(hi - lo)
                downers.append(owner)
            if len(tail):
                tail = np.asarray(tail, dtype=np.int64)
                res = tail[tail < dn]
                ship = tail[tail >= dn]
                if len(ship) and self.deleted:   # past the resident mask
                    ship = ship[~np.isin(
                        ship, np.fromiter(self.deleted, np.int64))]
                if len(res):
                    tres.append(self.positions(res).astype(np.int32))
                    tres_o.append(np.full(len(res), owner, np.int32))
                if len(ship):
                    tship.append(ship.astype(np.int32))
                    tship_o.append(np.full(len(ship), owner, np.int32))
            q_rows.extend(e.requests)
            q_owner.extend([owner] * len(e.requests))
        cat = (lambda xs: np.concatenate(xs) if xs
               else np.empty(0, np.int32))
        tres_i, tres_ow = cat(tres), cat(tres_o)
        tship_i, tship_ow = cat(tship), cat(tship_o)
        nd = sum(dlens)
        if nd + len(tres_i) + len(tship_i) == 0:
            return None
        rows = (self.vectors[tship_i.astype(np.int64)] if len(tship_i)
                else np.empty((0, queries.shape[1]), np.float32))
        # traffic accounting mirrors the padded buckets actually shipped;
        # candidate ids are the tails' own (the resident tail's floor
        # ships padding, no candidate)
        d_dim = queries.shape[1]
        qp, _, tr, ts, dp = ops.descriptor_extents(
            len(q_rows), dlens, len(tres_i), len(tship_i), self.chains)
        tf = self.traffic
        tf["query_bytes"] += qp * (d_dim * 4 + 4)
        tf["descriptor_bytes"] += dp * 12
        tf["candidate_id_bytes"] += (len(tres_i) + len(tship_i)) * 8
        tf["row_bytes"] += ts * d_dim * 4
        tf["bytes_to_device"] += (qp * (d_dim * 4 + 4) + dp * 12
                                  + (tr + ts) * 8 + ts * d_dim * 4)
        dstarts = np.asarray(dstarts, np.int32)
        dlens = np.asarray(dlens, np.int32)
        return ScanBatch(
            q_rows, np.asarray(q_owner, np.int32), dstarts, dlens,
            np.asarray(downers, np.int32), tres_i, tres_ow, tship_i,
            tship_ow, rows,
            None if len(tres_i) or len(tship_i) else self._desc_runs(
                dstarts, dlens))

    def _desc_runs(self, starts: np.ndarray, lens: np.ndarray
                   ) -> Optional[np.ndarray]:
        """The table position of each descriptor's first row, where
        every descriptor covers one run of consecutive positions; else
        None (and always in a runtime with chains)."""
        brk = self._run_breaks
        if brk is None or not len(starts):
            return None
        if np.any(brk[starts + lens - 1] != brk[starts]):
            return None
        return self.positions(self.base_ids[starts])

    def _count_scan(self, batch: ScanBatch, inplace_cols: int = 0) -> None:
        """Adds one descriptor launch of an assembled batch to the scan
        counters: its pairs to ``scan_pairs_*``, its descriptors to
        ``scan_descriptors``, and the launch to ``scan_launches`` and,
        in the in-place form (``inplace_cols`` columns), to
        ``scan_inplace_launches``."""
        from ..kernels import ops
        tf = self.traffic
        tf["scan_launches"] += 1
        tf["scan_inplace_launches"] += inplace_cols > 0
        tf["scan_descriptors"] += len(batch.lens)
        computed, needed = ops.scan_pairs(
            batch.q_owner, batch.lens, batch.owners, batch.tres_owners,
            batch.tship_owners, self.chains, inplace_cols)
        tf["scan_pairs_computed"] += computed
        tf["scan_pairs_needed"] += needed

    def _upload_scan_batch(self, queries, batch: ScanBatch, with_sel: bool):
        """Pad an assembled scan batch into its two host buffers
        (``ops.pad_descriptor_batch``) and ship them in ONE
        ``jax.device_put`` — with the device merge's index matrix when
        ``with_sel`` (a scan-only wave).  Returns the device ``(floats,
        ints)`` pair, its bucket key and the device ``sel`` (or None)."""
        import jax

        from ..kernels import ops
        b = batch
        host, key = ops.pad_descriptor_batch(
            queries[b.q_rows], b.q_owner, b.starts, b.lens, b.owners,
            b.tres, b.tres_owners, b.tship, b.rows, b.tship_owners,
            self.chains)
        sel = None
        if with_sel:
            per_req: Dict[int, List[int]] = {}
            for row, r in enumerate(b.q_rows):
                per_req.setdefault(r, []).append(row)
            # a floored wave merges one row per padded query row, so its
            # number of requests never picks the merge's program
            fixed = ops.floored(len(b.q_rows), len(b.lens), len(b.tres),
                                self.chains)
            sel = merge_sel([per_req[r] for r in sorted(per_req)], key[0],
                            key[0] if fixed else None)
        dev_batch, sel = jax.device_put((host, sel))
        self.traffic["host_device_calls"] += 1
        return dev_batch, key, sel

    def _scan_fp32(self, dev_batch, key: tuple, batch: ScanBatch, k: int):
        """One fp32 descriptor launch of an uploaded batch, in the
        in-place form where the batch has ``runs`` (no chains, no tail,
        every descriptor one run), else the gather form; counted in the
        scan counters.  Returns the device (vals, positions)."""
        from ..kernels import ops
        dev = self.to_device()
        cols = ops.inplace_columns(batch.runs, batch.lens, key, k,
                                   self.accum)
        out = ops.topk_segmented_desc(
            dev["vectors"], dev["base_ids"], dev["deleted"], dev_batch, key,
            k, metric=self.metric, accum=self.accum, inplace=cols > 0)
        self.traffic["host_device_calls"] += 1
        self._count_scan(batch, cols)
        return out

    def _execute_scan_device(self, queries, scan_items, k, launches,
                             dev_parts, wave: int = -1,
                             with_sel: bool = False):
        """ONE descriptor-driven segmented Pallas launch for every
        brute-forced candidate set in the batch — chain raw segments,
        OR-union scans, masked conjunction scans alike.  Entries with
        several sources expand into one query row per (request, source)
        pair; outputs stay on device for the merge fold.  Returns the
        merge's uploaded index matrix (``_upload_scan_batch``) or None."""
        from ..kernels import ops
        with span("assemble", wave=wave):
            t0 = time.perf_counter()
            flat = self._assemble_scan_batch(queries, scan_items)
            self.wave_times["upload_ms"] += (time.perf_counter() - t0) * 1e3
        if flat is None:
            return None
        with span("launch_scan", wave=wave):
            t0 = time.perf_counter()
            with span("upload", wave=wave):
                batch, key, sel = self._upload_scan_batch(queries, flat,
                                                          with_sel)
            v, g = self._scan_fp32(batch, key, flat, k)
            dt = time.perf_counter() - t0
            self.wave_times["launch_ms"] += dt * 1e3
        self._observe("scan", self._scan_units(scan_items), dt)
        self._emit_scan(flat.q_rows, v, g, launches, dev_parts)
        return sel

    @staticmethod
    def _emit_scan(q_rows, v, g, launches, dev_parts) -> None:
        li = len(launches)
        launches.append((v, g))
        for row, r in enumerate(q_rows):
            dev_parts[r].append((li, row))

    def _execute_scan_sq8(self, queries, scan_items, k, launches,
                          dev_parts, wave: int = -1,
                          with_sel: bool = False):
        """Default SQ8 scan path (``VectorMatonConfig.quantize='sq8'``):
        the whole batch's candidate sets run ONE segmented int8 launch
        against the resident quantized table, an fp32 rerank of the
        over-fetched top-kq, and the exactness certificate
        (``quant._sq8_topk_descriptors``).  A batch whose certificate
        fails on any query row is re-run through the fp32 descriptor
        path on the same uploaded buffers, so results always equal the
        fp32 scan's; ``sq8_stats`` counts certified vs escalated batches.
        After ``SQ8_MAX_STREAK`` consecutive escalations the scan runs
        fp32 outright: every launch of plain buckets, or, for a floored
        launch (``ops.floored``), its shape alone, since one floored shape
        holds a dense chain cover and the next a few rows.  A floored
        shape runs its fp32 program once with its first SQ8 batch, so
        neither an escalation nor a flip lowers a program inside
        serving, and the programs a runtime holds follow the shapes it
        met, not which of its batches certified.  Batches the
        eligibility gate rejects outright (metric/dim/k outside
        ``sq8_supported``) fall back to the fp32 path with a one-time
        warning.  Returns what ``_execute_scan_device`` does."""
        import jax

        from ..kernels import ops
        from ..kernels.quant import sq8_supported, topk_sq8_segmented_desc
        d_dim = int(queries.shape[1])
        if not sq8_supported(k, d_dim, self.metric):
            if not self._sq8_warned:
                warnings.warn(
                    f"sq8 scan path unsupported for k={k}, dim={d_dim}, "
                    f"metric={self.metric!r}; falling back to the fp32 "
                    "scan (recorded in sq8_stats['fallbacks'])",
                    RuntimeWarning, stacklevel=3)
                self._sq8_warned = True
            self.sq8_stats["fallbacks"] += 1
            return self._execute_scan_device(queries, scan_items, k,
                                             launches, dev_parts, wave,
                                             with_sel)
        overfetch = max(1, min(4, 128 // max(k, 1)))
        with span("assemble", wave=wave):
            t0 = time.perf_counter()
            flat = self._assemble_scan_batch(queries, scan_items)
            self.wave_times["upload_ms"] += (time.perf_counter() - t0) * 1e3
        if flat is None:
            return None
        dev = self.to_device()

        def fp32():
            return self._scan_fp32(batch, key, flat, k)

        with span("launch_scan", wave=wave):
            t0 = time.perf_counter()
            with span("upload", wave=wave):
                batch, key, sel = self._upload_scan_batch(queries, flat,
                                                          with_sel)
            runs = 1
            floored = ops.floored(len(flat.q_rows), len(flat.lens),
                                  len(flat.tres), self.chains)
            streak = key if floored else None
            if (self.sq8_escalate and self._sq8_bad_streak.get(streak, 0)
                    >= self.SQ8_MAX_STREAK):
                # the certificate keeps failing here: int8 scan plus
                # escalation is pure overhead, so serve fp32 directly
                self.sq8_stats["fallbacks"] += 1
                v, g = fp32()
            else:
                self.sq8_stats["batches"] += 1
                v, g, cert = topk_sq8_segmented_desc(
                    dev["vectors"], dev["quant"], dev["base_ids"],
                    dev["deleted"], batch, key, k, overfetch=overfetch)
                self.traffic["host_device_calls"] += 1
                self._count_scan(flat)
                warm = floored and key not in self._sq8_fp32_warm
                # without ``sq8_escalate`` (the approximate operating
                # point) the rerank is trusted and the certificate never
                # read back
                if self.sq8_escalate:
                    self.traffic["host_device_calls"] += 1
                    if bool(jax.device_get(cert).all()):  # device sync
                        self.sq8_stats["certified"] += 1
                        self._sq8_bad_streak[streak] = 0
                        if warm:
                            fp32()
                            runs = 2
                    else:
                        # quantization noise could have pushed a true
                        # top-k candidate out of the over-fetched set:
                        # redo the whole batch exactly
                        v, g = fp32()
                        runs = 2
                        self.sq8_stats["escalations"] += 1
                        self._sq8_bad_streak[streak] = (
                            self._sq8_bad_streak.get(streak, 0) + 1)
                if runs == 2:
                    self._sq8_fp32_warm.add(key)
            dt = time.perf_counter() - t0
            self.wave_times["launch_ms"] += dt * 1e3
        self._observe("scan", self._scan_units(scan_items), dt)
        self._emit_scan(flat.q_rows, v, g, launches, dev_parts)
        return sel

    # ---- graph states ------------------------------------------------- #

    def _execute_graphs_host(self, queries, graph_shared, graph_filtered,
                             k, ef_search, parts) -> None:
        for u, reqs in graph_shared.items():
            g = self.graph_objs[u]
            for r in reqs:
                d, i = g.search(queries[r], k, ef_search)
                parts[r].append((d, i))
        t0 = time.perf_counter()
        n_pairs = 0
        for u, allowed, reqs in graph_filtered:
            g = self.graph_objs[u]
            n_pairs += len(reqs)
            for r in reqs:
                d, i = g.search(queries[r], k, ef_search, allowed=allowed)
                parts[r].append((d, i))
        if n_pairs:
            self._observe("filtered_graph",
                          n_pairs * max(ef_search, k),
                          time.perf_counter() - t0)

    def _graph_fetch_width(self, k: int, ef_search: int
                           ) -> Tuple[int, int, bool]:
        """Tombstone over-fetch policy (DESIGN.md §3): over-fetch
        ``k + |deleted|`` rounded to a lane multiple, but NEVER past the
        beam's ef-list capacity — slots past ef can only be padding, and
        the old unbounded ``k + len(deleted)`` silently widened the beam
        (and retraced) per tombstone.  Past the capacity the executor
        switches to in-loop bitmap filtering (tombstones skipped in-scan,
        no over-fetch at all).  Returns (kk, ef_cap, bitmap_tombs)."""
        ef_cap = max(ef_search, k)
        n_del = len(self.deleted)
        if n_del == 0:
            return k, ef_cap, False
        if k + n_del <= ef_cap:
            return min(((k + n_del + 7) // 8) * 8, ef_cap), ef_cap, False
        return k, ef_cap, True

    def _execute_graphs_device(self, queries, graph_shared, graph_filtered,
                               k, ef_search, launches, dev_parts) -> None:
        """Beam searches, one fused launch per graph size bucket: all
        (graph, query) pairs against same-bucket states vmap together —
        filtered pairs (conjunction bitmaps, or the tombstone bitmap when
        the over-fetch clamp binds) in a second launch per bucket with the
        DISTINCT masks stacked once.  ``fuse_graphs=False`` falls back to
        one launch per state (the parity oracle)."""
        import jax.numpy as jnp

        from ..kernels import ops
        from .hnsw_jax import (hnsw_search_batch, hnsw_search_fused,
                               hnsw_search_fused_filtered)
        if not graph_shared and not graph_filtered:
            return
        dev = self.to_device()
        dn = self._dev_n
        kk, ef_cap, bitmap_tombs = self._graph_fetch_width(k, ef_search)
        d_dim = queries.shape[1]

        def emit(vals, gids, reqs):
            li = len(launches)
            launches.append((vals, gids))
            for row, r in enumerate(reqs):
                dev_parts[r].append((li, row))

        def compose_mask(allowed: Optional[np.ndarray]) -> np.ndarray:
            """(dn,) bool over table positions: candidate bitmap ∧
            ¬tombstones, host-composed.  ``None`` means tombstones-only
            (the clamp fallback)."""
            dmask = np.zeros(dn, dtype=bool)
            if self.deleted:
                gone = [i for i in self.deleted if i < dn]
                dmask[gone] = True
            if allowed is None:
                return ~dmask[self.table_rows(dn)]
            am = allowed
            if len(am) < dn:
                am = np.pad(am, (0, dn - len(am)))
            return (am[:dn] & ~dmask)[self.table_rows(dn)]

        if not self.fuse_graphs:
            # legacy per-state launches (parity oracle for the fused path)
            al = (jnp.asarray(compose_mask(None)) if bitmap_tombs
                  else None)
            self.traffic["host_device_calls"] += bitmap_tombs
            for u, reqs in graph_shared.items():
                h = dev["graphs"][u]
                d, i = hnsw_search_batch(
                    dev["vectors"], h["ids"], h["level0"], h["entry"],
                    jnp.asarray(queries[reqs]),
                    k=(k if bitmap_tombs else kk), ef=ef_cap,
                    metric=self.metric, allowed=al)
                ops.record_launch(
                    "graph_state", (u, len(reqs), kk, ef_cap, bitmap_tombs))
                self.traffic["host_device_calls"] += 2
                emit(d, i, reqs)
            for u, allowed, reqs in graph_filtered:
                h = dev["graphs"][u]
                t0 = time.perf_counter()
                d, i = hnsw_search_batch(
                    dev["vectors"], h["ids"], h["level0"], h["entry"],
                    jnp.asarray(queries[reqs]), k=k, ef=ef_cap,
                    metric=self.metric,
                    allowed=jnp.asarray(compose_mask(allowed)))
                self._observe("filtered_graph", len(reqs) * ef_cap,
                              time.perf_counter() - t0)
                ops.record_launch(
                    "graph_state_filt", (u, len(reqs), k, ef_cap))
                self.traffic["host_device_calls"] += 3
                emit(d, i, reqs)
            return

        # fused path: group (graph, query) pairs by size bucket
        plain: Dict[Tuple[int, int], Tuple[List[int], List[int]]] = {}
        filt: Dict[Tuple[int, int], dict] = {}

        def add_filtered(u, mask_key, allowed, reqs):
            bkey, slot = dev["graph_slot"][u]
            fr = filt.setdefault(bkey, {"masks": [], "mkey": {},
                                        "slots": [], "midx": [],
                                        "reqs": []})
            mi = fr["mkey"].get(mask_key)
            if mi is None:
                mi = len(fr["masks"])
                fr["mkey"][mask_key] = mi
                fr["masks"].append(compose_mask(allowed))
            for r in reqs:
                fr["slots"].append(slot)
                fr["midx"].append(mi)
                fr["reqs"].append(r)

        for u, reqs in graph_shared.items():
            if bitmap_tombs:
                add_filtered(u, "tombstones", None, reqs)
                continue
            bkey, slot = dev["graph_slot"][u]
            sl, rq = plain.setdefault(bkey, ([], []))
            for r in reqs:
                sl.append(slot)
                rq.append(r)
        for u, allowed, reqs in graph_filtered:
            add_filtered(u, id(allowed), allowed, reqs)

        for bkey, (slots, reqs) in plain.items():
            b = dev["graph_buckets"][bkey]
            p = len(reqs)
            p_pad = ops.bucket(p, 8)
            gi = np.zeros(p_pad, np.int32)
            gi[:p] = slots
            qm = np.zeros((p_pad, d_dim), np.float32)
            qm[:p] = queries[reqs]
            d, i = hnsw_search_fused(
                dev["vectors"], b["ids"], b["level0"], b["entry"],
                jnp.asarray(gi), jnp.asarray(qm), k=kk, ef=ef_cap,
                metric=self.metric)
            ops.record_launch("graph_fused",
                              (bkey, p_pad, kk, ef_cap, self.metric))
            self.traffic["query_bytes"] += p_pad * (d_dim * 4 + 4)
            self.traffic["bytes_to_device"] += p_pad * (d_dim * 4 + 4)
            self.traffic["host_device_calls"] += 3
            emit(d, i, reqs)          # rows past p: padding, never selected
        for bkey, fr in filt.items():
            b = dev["graph_buckets"][bkey]
            p = len(fr["reqs"])
            p_pad = ops.bucket(p, 8)
            gi = np.zeros(p_pad, np.int32)
            gi[:p] = fr["slots"]
            mi_arr = np.zeros(p_pad, np.int32)
            mi_arr[:p] = fr["midx"]
            qm = np.zeros((p_pad, d_dim), np.float32)
            qm[:p] = queries[fr["reqs"]]
            mn_pad = ops.bucket(len(fr["masks"]), 1)
            mm = np.zeros((mn_pad, dn), dtype=bool)
            for j, m in enumerate(fr["masks"]):
                mm[j] = m
            t0 = time.perf_counter()
            d, i = hnsw_search_fused_filtered(
                dev["vectors"], b["ids"], b["level0"], b["entry"],
                jnp.asarray(mm), jnp.asarray(mi_arr), jnp.asarray(gi),
                jnp.asarray(qm), k=k, ef=ef_cap, metric=self.metric)
            self._observe("filtered_graph", p * ef_cap,
                          time.perf_counter() - t0)
            ops.record_launch("graph_fused_filt",
                              (bkey, p_pad, mn_pad, k, ef_cap, self.metric))
            self.traffic["mask_bytes"] += mn_pad * dn
            self.traffic["query_bytes"] += p_pad * (d_dim * 4 + 4)
            self.traffic["bytes_to_device"] += (mn_pad * dn
                                                + p_pad * (d_dim * 4 + 4))
            self.traffic["host_device_calls"] += 5
            emit(d, i, fr["reqs"])

    # ---- residual verification (strategy c) --------------------------- #

    RESIDUAL_ROWS = 8            # query rows of one residual ranking launch

    def _launch_residual(self, queries, e: PlanEntry, s: CompiledSource):
        """Rank a residual source's live candidates for each of its
        requests on device, all of them (``ops.rank_candidates``; the
        ranking stays a device future); ``fetch`` verifies them in
        distance order.  The candidates upload once; the requests go
        ``RESIDUAL_ROWS`` to a launch, so a predicate's prefilter fixes
        one program whatever the wave holds.  Returns the
        ``PendingExecution.residuals`` records, none without a live
        candidate."""
        import jax

        from ..kernels import ops
        cand = self._live(s.ids)
        if len(cand) == 0:
            return []
        dev = self.to_device()
        cp, d_dim = ops.bucket(len(cand)), queries.shape[1]
        ids = np.zeros(cp, np.int32)
        ids[:len(cand)] = self.positions(cand)
        ship = np.nonzero(cand >= self._dev_n)[0]
        tp = ops.bucket(len(ship), 8) if len(ship) else 0
        tail_pos = np.full(tp, cp, np.int32)
        tail_pos[:len(ship)] = ship
        tail_rows = np.zeros((tp, d_dim), np.float32)
        tail_rows[:len(ship)] = self.vectors[cand[ship]]
        host = [ids, tail_pos, tail_rows]
        rows = self.RESIDUAL_ROWS
        chunks = [e.requests[lo:lo + rows]
                  for lo in range(0, len(e.requests), rows)]
        for reqs in chunks:
            x = np.zeros((rows, d_dim), np.float32)
            x[:len(reqs)] = queries[reqs]
            host.append(x)
        dev_ids, dev_pos, dev_rows, *dev_x = jax.device_put(host)
        out = []
        for reqs, x in zip(chunks, dev_x):
            ranked = ops.rank_candidates(dev["vectors"], x, dev_ids,
                                         len(cand), dev_pos, dev_rows,
                                         metric=self.metric)
            out.append((replace(e, requests=reqs), s, cand, ranked))
        self.traffic["host_device_calls"] += 1 + len(chunks)
        self.traffic["query_bytes"] += len(chunks) * rows * d_dim * 4
        self.traffic["candidate_id_bytes"] += ids.nbytes
        self.traffic["row_bytes"] += tail_rows.nbytes
        self.traffic["bytes_to_device"] += sum(a.nbytes for a in host)
        return out

    def _verify_residuals(self, pending: PendingExecution) -> None:
        """Fetch half of device-ranked residual sources: download each
        ranking and verify it in distance order (``_verify_ranked``)."""
        import jax
        for e, s, cand, ranked in pending.residuals:
            d, li = jax.device_get(ranked)
            self.traffic["host_device_calls"] += 1
            n = len(e.requests)
            self._verify_ranked(e, s, cand, lambda m: (d[:n, :m], li[:n, :m]),
                                len(cand), pending.k, pending.parts)

    def _execute_residual(self, queries, e: PlanEntry, s: CompiledSource,
                          k: int, parts) -> None:
        """Host backend: the (Q, |cand|) distance matrix once, then
        ``_verify_ranked`` over its top-m, m doubling from 4k (or the
        whole prefilter where the planner replays a measured yield
        collapse, ``CompiledSource.residual_full``)."""
        cand = self._live(s.ids)
        if len(cand) == 0:
            return
        x = np.asarray(queries[e.requests], dtype=np.float32)
        y = np.asarray(self.vectors[cand], dtype=np.float32)
        if self.metric == "l2":
            dmat = (np.sum(x * x, axis=1, keepdims=True)
                    + np.sum(y * y, axis=1) - 2.0 * (x @ y.T))
            np.maximum(dmat, 0.0, out=dmat)
        else:
            dmat = -(x @ y.T)

        def rank(m: int) -> Tuple[np.ndarray, np.ndarray]:
            part = np.argpartition(dmat, m - 1, axis=1)[:, :m]
            pv = np.take_along_axis(dmat, part, axis=1)
            order = np.argsort(pv, axis=1, kind="stable")
            return (np.take_along_axis(pv, order, axis=1),
                    np.take_along_axis(part, order, axis=1))

        full = s.residual_full and self._adaptive
        self._verify_ranked(e, s, cand, rank,
                            len(cand) if full else min(len(cand), 4 * k),
                            k, parts)

    @property
    def _adaptive(self) -> bool:
        return (self.planner is not None
                and getattr(self.planner, "adaptive", False))

    def _verify_ranked(self, e: PlanEntry, s: CompiledSource,
                       cand: np.ndarray, rank, m: int, k: int,
                       parts) -> None:
        """Exact host verification in distance order: ``rank(m)`` gives
        each request's top-m candidates (ascending distances, positions
        into ``cand``); hits are verified in that order, doubling m until
        every request has k verified hits or the prefilter is exhausted.
        The ranking is prefix-stable in m, so the answers do not depend
        on where m starts.

        Adaptive escalation (DESIGN.md §11): the loop tracks observed
        verification yield; when a row's projected need ``k/yield``
        already covers the whole prefilter — the doubling ramp would
        provably walk every candidate anyway — it jumps straight to the
        full ranking, reports the switch to the planner
        (``planner_residual_switches``) and remembers it per (predicate,
        delta version) so a re-compile starts there
        (``CompiledSource.residual_full``)."""
        t_start = time.perf_counter()
        seqs = self.sequences
        cache: Dict[int, bool] = {}

        def ok(gid: int) -> bool:
            v = cache.get(gid)
            if v is None:
                v = bool(s.verify.matches(seqs[gid], self._attrs_of(gid)))
                cache[gid] = v
            return v

        reqs = e.requests
        adaptive = self._adaptive
        while True:
            d, li = rank(m)
            done = True
            checked = cnt = 0
            for row in range(len(reqs)):
                cnt = checked = 0
                for c in li[row]:
                    if c < 0:
                        break
                    checked += 1
                    if ok(int(cand[c])):
                        cnt += 1
                        if cnt >= k:
                            break
                if cnt < k:
                    done = False
                    break
            if done or m >= len(cand):
                break
            grown = min(2 * m, len(cand))
            if adaptive and checked:
                # yield-collapse switch: the failing row verified cnt of
                # checked ranked candidates, so it needs ~k·checked/cnt
                # ranked rows; once that projection covers the whole
                # prefilter AND the next doubling wouldn't, escalate to
                # the full scan in one step
                need = (k * checked) // max(cnt, 1)
                if need >= len(cand) and grown < len(cand):
                    m = len(cand)
                    s.residual_full = True
                    self.planner.note_residual_switch(
                        e.key, int(self.delta.version))
                    continue
            m = grown
        for row, r in enumerate(reqs):
            vd: List[float] = []
            vi: List[int] = []
            for pos, c in enumerate(li[row]):
                if c < 0:
                    break
                gid = int(cand[c])
                if ok(gid):
                    vd.append(float(d[row][pos]))
                    vi.append(gid)
                    if len(vi) == k:
                        break
            parts[r].append((np.asarray(vd, np.float32),
                             np.asarray(vi, np.int64)))
        dt = time.perf_counter() - t_start
        self.residual_stats["rows_verified"] += len(cache)
        self.wave_times["residual_verify_ms"] += dt * 1e3
        self._observe("residual", m * len(reqs), dt)

    # ------------------------------------------------------------------ #
    # accounting
    # ------------------------------------------------------------------ #

    def chain_ids(self, state: int) -> np.ndarray:
        """V_state reconstructed from the CSR chain cover (Lemma 4)."""
        segs = []
        u = state
        while u != -1:
            segs.append(self.base_ids[self.base_lo[u]:self.base_hi[u]])
            u = int(self.inherit[u])
        return (np.concatenate(segs) if segs else np.empty(0, np.int64))

    def stats(self) -> Dict[str, int]:
        return {
            "states": len(self.kind),
            "raw_states": int((self.kind == KIND_RAW).sum()),
            "graph_states": int((self.kind == KIND_GRAPH).sum()),
            "base_entries": len(self.base_ids),
            "attr_segments": self.n_csr - self.n_states,
            "device_resident": int(self._dev is not None),
            "generation": self.generation,
            "delta_pending": self.delta.pending,
        }
