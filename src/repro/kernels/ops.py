"""Public jit'd wrappers around the Pallas kernels (DESIGN.md §2–§3).

Responsibilities:
  * pad ragged (Q, N, k) to hardware-aligned tile multiples and strip the
    padding from results (padded base rows get +inf distance / -1 index);
  * **shape-bucket** every dynamic dimension (query rows, candidate rows,
    descriptor counts) to power-of-two buckets so steady-state serving
    hits a fixed set of compiled executables instead of retracing XLA on
    every novel batch shape (DESIGN.md §3 "launch cache");
  * drive the **descriptor-resolved** segmented kernel
    (``topk_segmented_desc``): candidate sets arrive as ``(seg_start,
    seg_len, owner)`` triples against the device-resident CSR, so frozen
    chain covers and scan unions ship zero candidate-id bytes per batch —
    only post-watermark delta tails cross the host↔device boundary;
  * account every launch and (re)trace in module-level counters
    (``launch_stats``) that ``VectorMaton.maintenance_stats`` and the
    benchmark gate read;
  * select interpret mode automatically off-TPU (interpret=True executes
    the kernel body in Python for validation; on a TPU it compiles);
  * expose a NumPy fast path used by the CPU benchmark harness so the paper's
    QPS experiments aren't bottlenecked by interpret-mode overhead — the
    Pallas path is the TPU deployment path and is what tests validate.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .distance_topk import (distance_topk, distance_topk_descriptors,
                            distance_topk_segmented, packed_int_sizes,
                            segmented_dense_topk)
from .pairwise import pairwise_distance
from .tuning import MAX_BLOCK_N, default_impl, default_interpret, \
    select_tiles

_LANE = 128
DESC_FLOOR = 64     # descriptors a floored launch holds before doubling


# --------------------------------------------------------------------- #
# launch cache: power-of-two shape buckets + launch/retrace accounting
# --------------------------------------------------------------------- #

def bucket(n: int, floor: int = _LANE) -> int:
    """Smallest power-of-two multiple of ``floor`` holding ``n`` rows (0
    stays 0).  Every dynamic dimension the executor feeds a kernel goes
    through this, so a steady-state batch sweep compiles O(log) distinct
    executables per dimension instead of one per novel shape."""
    if n <= 0:
        return 0
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return b


_launch_counters: Dict[str, int] = {}
_launch_keys: set = set()


def record_launch(kind: str, key: Tuple) -> None:
    """Count one kernel launch of ``kind``; a (kind, key) pair not seen
    since the last reset is a (re)trace — a new executable compiled."""
    _launch_counters[kind] = _launch_counters.get(kind, 0) + 1
    _launch_counters["launches"] = _launch_counters.get("launches", 0) + 1
    if (kind, key) not in _launch_keys:
        _launch_keys.add((kind, key))
        _launch_counters["retraces"] = (
            _launch_counters.get("retraces", 0) + 1)


def launch_stats() -> Dict[str, int]:
    """Launch/retrace counters since the last reset.  ``executables`` is
    the number of distinct (kind, shape-bucket) keys seen — the bound the
    retrace-regression test asserts against."""
    out = dict(_launch_counters)
    out.setdefault("launches", 0)
    out.setdefault("retraces", 0)
    out["executables"] = len(_launch_keys)
    return out


def launch_keys() -> Dict[str, list]:
    """Distinct shape-bucket keys seen since the last reset, per launch
    kind — the scan kinds end in the top-k core (``"pallas"``/``"xla"``)
    that ran."""
    out: Dict[str, list] = {}
    for kind, key in sorted(_launch_keys, key=repr):
        out.setdefault(kind, []).append(key)
    return out


def reset_launch_stats() -> None:
    _launch_counters.clear()
    _launch_keys.clear()


def jit_cache_sizes() -> Dict[str, int]:
    """Tracing-cache sizes of the jit'd kernel entry points — the ground
    truth the bucket counters approximate (tests compare both)."""
    from ..core import hnsw_jax
    out = {}
    for name, fn in [
            ("distance_topk_segmented", distance_topk_segmented),
            ("distance_topk_descriptors", distance_topk_descriptors),
            ("hnsw_search_fused", hnsw_jax.hnsw_search_fused),
            ("hnsw_search_fused_filtered",
             hnsw_jax.hnsw_search_fused_filtered),
    ]:
        out[name] = int(fn._cache_size())
    return out


def _pad_to(x: jax.Array, rows: int) -> jax.Array:
    if x.shape[0] == rows:
        return x
    pad = rows - x.shape[0]
    return jnp.pad(x, ((0, pad), (0, 0)))


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def pairwise_sqdist(x: jax.Array, y: jax.Array, *, metric: str = "l2",
                    interpret: bool | None = None,
                    accum: str = "f32") -> jax.Array:
    """(Q, d) × (N, d) -> (Q, N) distances via the tiled Pallas kernel."""
    if interpret is None:
        interpret = default_interpret()
    q, n = x.shape[0], y.shape[0]
    bq, bn = select_tiles(q, n, x.shape[1],
                          itemsize=2 if accum == "bf16" else 4)
    qp, np_ = _round_up(max(q, 1), bq), _round_up(max(n, 1), bn)
    out = pairwise_distance(_pad_to(x, qp), _pad_to(y, np_), metric=metric,
                            block_q=bq, block_n=bn, interpret=interpret,
                            accum=accum)
    return out[:q, :n]


def topk(x: jax.Array, y: jax.Array, k: int, *, metric: str = "l2",
         interpret: bool | None = None, accum: str = "f32"
         ) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k via the fused streaming kernel.

    Padded base rows are pushed to +inf so they can never be selected unless
    k > N, in which case trailing entries are (-1, inf) — callers treat index
    -1 as "no neighbour".
    """
    if interpret is None:
        interpret = default_interpret()
    q, n = x.shape[0], y.shape[0]
    kp = _round_up(k, 8)  # nearby k share one compiled kernel
    if kp > _LANE:
        raise ValueError(f"k={k} exceeds kernel max {_LANE}")
    bq, bn = select_tiles(q, n, x.shape[1], k=kp,
                          itemsize=2 if accum == "bf16" else 4)
    qp, np_ = _round_up(max(q, 1), bq), _round_up(max(n, 1), bn)
    xpad = _pad_to(x, qp)
    ypad = _pad_to(y, np_)
    vals, idx = distance_topk(xpad, ypad, kp, metric=metric,
                              block_q=bq, block_n=bn,
                              interpret=interpret, valid_n=n, accum=accum)
    vals, idx = vals[:q, :k], idx[:q, :k]
    # mask padded base rows
    invalid = idx >= n
    vals = jnp.where(invalid, jnp.inf, vals)
    idx = jnp.where(invalid, -1, idx)
    return vals, idx


def topk_segmented(x: jax.Array, y: jax.Array, qseg: jax.Array,
                   cseg: jax.Array, k: int, *, metric: str = "l2",
                   interpret: bool | None = None, accum: str = "f32"
                   ) -> Tuple[jax.Array, jax.Array]:
    """Segmented exact top-k: ONE kernel launch serving many (query, id-set)
    pairs.  ``qseg`` (Q,) assigns each query row an owner id; ``cseg`` (N,)
    assigns each candidate row an owner id; query r ranks only candidates c
    with cseg[c] == qseg[r].  Owner ids must be >= 0; use qseg -1 for rows
    that should match nothing.

    Returns (Q, k) distances ascending + candidate-row indices into ``y``;
    unfilled slots (segment smaller than k, or empty) are (+inf, -1).
    """
    if interpret is None:
        interpret = default_interpret()
    q, n = x.shape[0], y.shape[0]
    kp = _round_up(k, 8)
    if kp > _LANE:
        raise ValueError(f"k={k} exceeds kernel max {_LANE}")
    bq, bn = select_tiles(q, n, x.shape[1], k=kp,
                          itemsize=2 if accum == "bf16" else 4)
    qp, np_ = _round_up(max(q, 1), bq), _round_up(max(n, 1), bn)
    qseg = jnp.asarray(qseg, jnp.int32)
    cseg = jnp.asarray(cseg, jnp.int32)
    # Padded query rows own segment -1, padded candidate rows -2: neither
    # matches anything, so padding can never be selected.
    qseg_p = jnp.full((qp, 1), -1, jnp.int32).at[:q, 0].set(qseg)
    cseg_p = jnp.full((1, np_), -2, jnp.int32).at[0, :n].set(cseg)
    vals, idx = distance_topk_segmented(
        _pad_to(x, qp), _pad_to(y, np_), qseg_p, cseg_p, kp, metric=metric,
        block_q=bq, block_n=bn, interpret=interpret, valid_n=n,
        accum=accum)
    vals, idx = vals[:q, :k], idx[:q, :k]
    invalid = (idx < 0) | ~jnp.isfinite(vals)
    vals = jnp.where(invalid, jnp.inf, vals)
    idx = jnp.where(invalid, -1, idx)
    return vals, idx


def desc_tiles(key: Tuple, k: int, accum: str = "f32") -> Tuple[int, int]:
    """``(block_q, block_n)`` of a descriptor launch with bucket key
    ``key``: the flat candidate extent is fixed by the pre-bucketed
    regions, so block_n must divide it; block_q divides the padded Q."""
    qp, n_desc, tr, ts, _, d = key
    bq, bn = select_tiles(qp, n_desc + tr + ts, d, k=_round_up(k, 8),
                          itemsize=2 if accum == "bf16" else 4,
                          divisor_n=max(n_desc + tr + ts, _LANE))
    return min(bq, qp), bn


def inplace_columns(runs, desc_lens, key: Tuple, k: int,
                    accum: str = "f32") -> int:
    """Candidate columns the in-place form of a descriptor launch
    evaluates: the aligned ``block_n`` blocks that cover each
    descriptor's run of table positions [runs[d], runs[d] +
    desc_lens[d]), times block_n.  0 where there are no runs (``None``):
    the launch then takes the gather form."""
    if runs is None or not len(runs):
        return 0
    bn = desc_tiles(key, k, accum)[1]
    runs = np.asarray(runs, np.int64)
    last = runs + np.asarray(desc_lens, np.int64) - 1
    return int(np.sum(last // bn - runs // bn + 1)) * bn


def topk_segmented_desc(vectors: jax.Array, base_ids: jax.Array,
                        deleted: jax.Array, batch, key: Tuple, k: int, *,
                        metric: str = "l2",
                        interpret: bool | None = None,
                        accum: str = "f32", impl: str | None = None,
                        inplace: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """Descriptor-driven segmented top-k: ONE launch serving many
    (query, id-set) pairs whose frozen-base candidates are ``(seg_start,
    seg_len, owner)`` triples resolved against the device-resident CSR.

    ``batch`` is the ``(floats, ints)`` pair of ``pad_descriptor_batch``,
    uploaded by the caller in one ``jax.device_put``, and ``key`` its
    bucket key.  Host→device traffic is the query matrix plus planning
    integers (the descriptor triples, owner ids, and tail id lists);
    candidate rows for the descriptor region and the resident tail are
    gathered on device.  Only the shipped tail's rows — delta inserts
    past the upload watermark — ship vector rows, and the caller must
    pre-filter their tombstones.

    Every dynamic dimension is padded to a power-of-two bucket (``bucket``)
    so repeated batches of similar size reuse one compiled executable.
    ``inplace`` takes the in-place form (``distance_topk_descriptors``),
    for a launch whose descriptors are runs of table positions
    (``inplace_columns``).
    Returns DEVICE arrays ``(vals, gids)`` of shape (qp, k), one row per
    padded query row (the caller keeps the first Q): ascending distances
    + the candidates' rows of ``vectors``, (+inf, -1) padding.
    """
    if interpret is None:
        interpret = default_interpret()
    if impl is None:
        impl = default_impl()
    kp = _round_up(k, 8)
    if kp > _LANE:
        raise ValueError(f"k={k} exceeds kernel max {_LANE}")
    bq, bn = desc_tiles(key, k, accum)
    vals, gids = distance_topk_descriptors(
        vectors, base_ids, deleted, *batch, k=k, n_desc=key[1], packed=key,
        metric=metric, block_q=bq, block_n=bn, interpret=interpret,
        accum=accum, impl=impl, inplace=inplace)
    record_launch("desc_scan", key + (kp, metric, inplace, impl))
    return vals, gids


def floored(q: int, n_descriptors: int, n_res: int,
            chains: bool = False) -> bool:
    """Whether a descriptor launch takes the shape floors: the runtime's
    chain covers can walk several states (``chains``: rows with
    sequences of their own), or its query rows carry more descriptors
    than rows, or it has a resident tail (masked scans).  Such waves mix
    many small covers, and the sums of their extents would otherwise
    pick a program per mix; on the floors only the candidate region
    moves, so each predicate's own bursts compile every program.  A wave
    of one segment per query row and no tail in a runtime without chains
    (a label per row and per request) keeps plain power-of-two buckets:
    it is made of a few large segments, whose bursts compile every shape
    it takes, and the floors' padding cost its large extents 9-12 % of
    the median latency in label deployments on a TPU v5e."""
    return chains or n_res > 0 or n_descriptors > q


def descriptor_extents(q: int, desc_lens, n_res: int, n_ship: int,
                       chains: bool = False
                       ) -> Tuple[int, int, int, int, int]:
    """Bucketed extents ``(qp, n_desc, tr, ts, dp)`` of a descriptor
    launch: query rows, then the descriptor region, the resident tail
    and the shipped tail of the flat candidate layout, and the number of
    descriptors.  In a ``floored`` wave the resident tail and the
    descriptor count have floors that a wave seldom passes, so a wave
    that mixes chain covers and masked scans keeps the programs its
    predicates' own bursts compiled.  The tail's floor is one whole
    column tile (``MAX_BLOCK_N``): the tile must divide the flat extent,
    and a smaller floor beside a power-of-two descriptor region would
    shrink it.  The descriptor floor (``DESC_FLOOR``) holds a wave of a
    few dozen chain covers at the two or three runs ``chain_layout``
    leaves each."""
    nd_real = int(np.sum(desc_lens)) if len(desc_lens) else 0
    n_desc = bucket(nd_real)
    if not floored(q, len(desc_lens), n_res, chains):
        return (bucket(q), n_desc, 0, bucket(n_ship),
                bucket(len(desc_lens), 8) if n_desc else 0)
    return (bucket(q), n_desc, bucket(max(n_res, 1), MAX_BLOCK_N),
            bucket(n_ship),
            bucket(len(desc_lens), DESC_FLOOR) if n_desc else 0)


def scan_pairs(qseg: np.ndarray, desc_lens: np.ndarray,
               desc_owners: np.ndarray, tail_res_owners: np.ndarray,
               tail_ship_owners: np.ndarray, chains: bool = False,
               inplace_cols: int = 0) -> Tuple[int, int]:
    """(pairs computed, pairs needed) of one descriptor launch, fp32 or
    SQ8.  The gather form's grid evaluates every padded query row
    against every padded candidate slot: the owner mask only discards a
    pair's distance, and no tile is skipped.  The in-place form
    evaluates its ``inplace_cols`` columns (``inplace_columns``) and
    skips its padding tiles.  A real query row needs the
    candidates its owner's descriptors and tails name (tombstones
    inside a frozen segment included: the host does not look inside
    segments)."""
    qp, n_desc, tr, ts, _ = descriptor_extents(
        len(qseg), desc_lens, len(tail_res_owners), len(tail_ship_owners),
        chains)
    m = int(qseg.max()) + 1 if len(qseg) else 0
    rows = (np.bincount(desc_owners, weights=desc_lens, minlength=m)[:m]
            + np.bincount(tail_res_owners, minlength=m)[:m]
            + np.bincount(tail_ship_owners, minlength=m)[:m])
    return qp * (inplace_cols or n_desc + tr + ts), int(rows[qseg].sum())


def pad_descriptor_batch(x, qseg, desc_starts, desc_lens, desc_owners,
                         tail_res_ids, tail_res_owners, tail_ship_ids,
                         tail_ship_rows, tail_ship_owners,
                         chains: bool = False):
    """Bucket-pad the host-side inputs of a descriptor launch (shared by
    the fp32 and SQ8 scans) into the two host buffers one
    ``jax.device_put`` ships (layout: ``distance_topk.packed_int_sizes``):
    ``floats``, the query rows then the shipped tail's rows, and
    ``ints``, every planning integer end to end.  Returns ``((floats,
    ints), key)`` with the shape bucket key ``(qp, n_desc, tr, ts, dp,
    d)``, which fixes every offset; ``chains`` as in ``floored``."""
    q, d = x.shape
    qp, n_desc, tr, ts, dp = descriptor_extents(
        q, desc_lens, len(tail_res_ids), len(tail_ship_ids), chains)
    if n_desc + tr + ts == 0:
        raise ValueError("descriptor launch with no candidates")
    key = (qp, n_desc, tr, ts, dp, d)
    floats = np.zeros((qp + ts, d), np.float32)
    floats[:q] = x
    floats[qp:qp + len(tail_ship_rows)] = tail_ship_rows
    ints = np.empty(sum(packed_int_sizes(key)), np.int32)
    at = 0
    # padded query rows own -1, padded candidate slots -3: neither matches
    for a, n, fill in zip(
            (qseg, desc_starts, desc_lens, desc_owners, tail_res_ids,
             tail_res_owners, tail_ship_ids, tail_ship_owners),
            packed_int_sizes(key), (-1, 0, 0, -3, 0, -3, 0, -3)):
        ints[at:at + len(a)] = a
        ints[at + len(a):at + n] = fill
        at += n
    return (floats, ints), key


# --------------------------------------------------------------------- #
# XLA-compiled twins: the non-interpret path off-TPU.  Pallas lowers
# natively only on TPU; everywhere else these jnp twins are what
# "compiled kernels" means — one XLA executable per shape bucket, MXU/
# AVX matmul + lax.top_k, the same output contract as the Pallas
# wrappers (DESIGN.md §6).
# --------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _topk_dense_xla(x, y, k: int, metric: str):
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    xy = jax.lax.dot_general(xf, yf, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    if metric == "l2":
        x2 = jnp.sum(xf * xf, axis=-1, keepdims=True)
        y2 = jnp.sum(yf * yf, axis=-1)[None, :]
        dist = jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)
    else:
        dist = -xy
    neg, idx = jax.lax.top_k(-dist, k)
    return -neg, idx


def topk_xla(x: jax.Array, y: jax.Array, k: int, *, metric: str = "l2"
             ) -> Tuple[jax.Array, jax.Array]:
    """XLA-compiled dense top-k twin of ``topk`` (same sentinel contract:
    trailing (+inf, -1) when k > N)."""
    q, n = x.shape[0], y.shape[0]
    kk = min(k, n)
    vals, idx = _topk_dense_xla(x, y, kk, metric)
    if kk < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - kk)),
                       constant_values=jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - kk)), constant_values=-1)
    return vals, idx


_topk_segmented_xla_jit = jax.jit(segmented_dense_topk,
                                  static_argnames=("k", "metric"))


def topk_segmented_xla(x: jax.Array, y: jax.Array, qseg, cseg, k: int, *,
                       metric: str = "l2") -> Tuple[jax.Array, jax.Array]:
    """XLA-compiled twin of ``topk_segmented`` (dense segmented sweep —
    the same core the sharded executor runs inside ``shard_map``)."""
    return _topk_segmented_xla_jit(x, y, jnp.asarray(qseg, jnp.int32),
                                   jnp.asarray(cseg, jnp.int32), k,
                                   metric=metric)


# --------------------------------------------------------------------- #
# NumPy fast path (host benchmarks; bit-compatible with ref.py in f32)
# --------------------------------------------------------------------- #

def topk_numpy(x: np.ndarray, y: np.ndarray, k: int, *, metric: str = "l2"
               ) -> Tuple[np.ndarray, np.ndarray]:
    x = np.asarray(x, dtype=np.float32)
    y = np.asarray(y, dtype=np.float32)
    if metric == "l2":
        d = (np.sum(x * x, axis=1, keepdims=True) + np.sum(y * y, axis=1)
             - 2.0 * (x @ y.T))
        np.maximum(d, 0.0, out=d)
    else:
        d = -(x @ y.T)
    k_eff = min(k, y.shape[0])
    part = np.argpartition(d, k_eff - 1, axis=1)[:, :k_eff]
    pv = np.take_along_axis(d, part, axis=1)
    order = np.argsort(pv, axis=1, kind="stable")
    idx = np.take_along_axis(part, order, axis=1)
    vals = np.take_along_axis(pv, order, axis=1)
    if k_eff < k:
        pad = k - k_eff
        vals = np.pad(vals, ((0, 0), (0, pad)), constant_values=np.inf)
        idx = np.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
    return vals, idx


def topk_segmented_numpy(x: np.ndarray, y: np.ndarray, qseg: np.ndarray,
                         cseg: np.ndarray, k: int, *, metric: str = "l2"
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Host reference for ``topk_segmented`` (same output contract)."""
    x = np.asarray(x, dtype=np.float32)
    y = np.asarray(y, dtype=np.float32)
    qseg = np.asarray(qseg, dtype=np.int64)
    cseg = np.asarray(cseg, dtype=np.int64)
    q = x.shape[0]
    vals = np.full((q, k), np.inf, dtype=np.float32)
    idx = np.full((q, k), -1, dtype=np.int32)
    for r in range(q):
        if qseg[r] < 0:
            continue
        cols = np.nonzero(cseg == qseg[r])[0]
        if len(cols) == 0:
            continue
        v, li = topk_numpy(x[r:r + 1], y[cols], min(k, len(cols)),
                           metric=metric)
        valid = li[0] >= 0
        m = int(valid.sum())
        vals[r, :m] = v[0][valid]
        idx[r, :m] = cols[li[0][valid]]
    return vals, idx


# --------------------------------------------------------------------- #
# residual verification: a prefilter ranked on device
# --------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnames=("metric",))
def rank_candidates(vectors: jax.Array, x: jax.Array, cand: jax.Array,
                    n_valid, tail_pos: jax.Array, tail_rows: jax.Array, *,
                    metric: str = "l2"):
    """Every query row's candidates in ascending distance, for residual
    verification: ``x`` (Q, d) query rows, ``cand`` (C,) row ids of which
    the first ``n_valid`` are real, gathered from the resident
    ``vectors`` except at ``tail_pos``, whose rows ``tail_rows`` ship
    from the host (inserts past the upload watermark; padding positions
    point past C).  Returns (Q, C) distances and positions into
    ``cand``, padding last at +inf.  Q, C and the tail come bucketed, so
    a predicate's prefilter size fixes one program; ``n_valid`` is
    traced, not a shape."""
    with jax.named_scope("vm/residual_rank"):
        y = vectors[jnp.minimum(cand, vectors.shape[0] - 1)]
        if tail_rows.shape[0]:
            y = y.at[tail_pos].set(tail_rows, mode="drop")
        xy = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
        if metric == "l2":
            d = jnp.maximum(jnp.sum(x * x, 1, keepdims=True)
                            + jnp.sum(y * y, 1) - 2.0 * xy, 0.0)
        else:
            d = -xy
        pos = jnp.broadcast_to(jnp.arange(cand.shape[0], dtype=jnp.int32),
                               d.shape)
        d = jnp.where(pos < n_valid, d, jnp.inf)
        return jax.lax.sort((d, pos), dimension=1, is_stable=True,
                            num_keys=1)


# --------------------------------------------------------------------- #
# device-side merge: segmented dedup + top-k fold over launch outputs
# --------------------------------------------------------------------- #

_ID_SENTINEL = np.int32(2 ** 31 - 1)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk_device(big_d: jax.Array, big_i: jax.Array, sel: jax.Array,
                      deleted: jax.Array, k: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Per-request merge of kernel/beam launch outputs, entirely on device.

    ``big_d``/``big_i``: (T, W) launch output rows (distances + global
    ids, (-1, +inf) padding) — one launch's outputs as they are, or
    several stacked; ``sel``: (R, S) row indices into them — request r's
    candidate pool is rows ``sel[r]`` flattened, in the same order the
    host merge would concatenate them (so tie-breaks are bit-identical);
    out-of-pool slots hold T, the all-padding row the program appends.
    ``deleted`` is the resident tombstone mask (ids past it must be
    pre-filtered by the caller, as in the scan path).

    Per request: drop tombstones, stable-sort by distance, keep the first
    (closest) occurrence per id — OR disjuncts and graph/scan overlap can
    duplicate ids — and cut to k.  Matches the NumPy host merge
    bit-for-bit; ``tests/test_device_exec.py`` asserts it on the churn
    oracle workload.
    """
    with jax.named_scope("vm/merge"):
        big_d = jnp.concatenate(
            [big_d, jnp.full((1, big_d.shape[1]), jnp.inf, big_d.dtype)])
        big_i = jnp.concatenate(
            [big_i, jnp.full((1, big_i.shape[1]), -1, big_i.dtype)])
        r_n, s_n = sel.shape
        d = big_d[sel].reshape(r_n, -1)
        i = big_i[sel].reshape(r_n, -1)
        dn = int(deleted.shape[0])
        dead = (i >= 0) & (i < dn) & deleted[jnp.clip(i, 0, max(dn - 1, 0))]
        bad = (i < 0) | dead | ~jnp.isfinite(d)
        d = jnp.where(bad, jnp.inf, d)
        iu = jnp.where(bad, _ID_SENTINEL, i)

        def one(drow, irow):
            p1 = jnp.argsort(drow, stable=True)
            ds, is_ = drow[p1], irow[p1]
            p2 = jnp.argsort(is_, stable=True)    # ids grouped, d-order ties
            idg = is_[p2]
            first = jnp.concatenate(
                [jnp.ones((1,), bool), idg[1:] != idg[:-1]])
            first = first & (idg != _ID_SENTINEL)
            keep = jnp.zeros_like(first).at[p2].set(first)   # back to d-order
            rank = jnp.cumsum(keep) - 1
            slot = jnp.where(keep & (rank < k), rank, k)
            out_d = jnp.full((k + 1,), jnp.inf, jnp.float32).at[slot].set(ds)
            out_i = jnp.full((k + 1,), -1, jnp.int32).at[slot].set(
                jnp.where(is_ == _ID_SENTINEL, -1, is_))
            return out_d[:k], out_i[:k]

        return jax.vmap(one)(d, iu)


def merge_topk_allgather(vals: jax.Array, gids: jax.Array, axis: str,
                         k: int) -> Tuple[jax.Array, jax.Array]:
    """Cross-shard top-k fold, on device, inside a ``shard_map`` body —
    the all-gather extension of the device merge (DESIGN.md §5).

    ``vals``/``gids``: this shard's (Q, k) local winners with (+inf, -1)
    sentinel padding.  All shards' winners are gathered into a
    (Q, shards·k) pool and reduced with one ``lax.top_k``; collective
    volume is O(shards · Q · k · 8 bytes) per launch, independent of the
    table size.  Shard candidate sets are disjoint (every global id lives
    on exactly one shard), so no id-dedup pass is needed — sentinels sort
    last and are re-stamped (+inf, -1) so a pool with fewer than k live
    rows returns the same padding the NumPy merge emits.
    """
    av = jax.lax.all_gather(vals, axis, axis=0)      # (shards, Q, k)
    ai = jax.lax.all_gather(gids, axis, axis=0)
    q = vals.shape[0]
    av = av.transpose(1, 0, 2).reshape(q, -1)
    ai = ai.transpose(1, 0, 2).reshape(q, -1)
    neg, pos = jax.lax.top_k(-av, k)
    out_v = -neg
    out_i = jnp.take_along_axis(ai, pos, axis=1)
    bad = ~jnp.isfinite(out_v) | (out_i < 0)
    return (jnp.where(bad, jnp.inf, out_v),
            jnp.where(bad, -1, out_i))


__all__ = ["pairwise_sqdist", "topk", "topk_segmented",
           "topk_segmented_desc", "inplace_columns", "topk_xla",
           "topk_segmented_xla",
           "segmented_dense_topk", "topk_segmented_numpy", "topk_numpy",
           "merge_topk_device", "merge_topk_allgather", "bucket",
           "default_interpret", "default_impl", "select_tiles",
           "launch_stats", "launch_keys", "reset_launch_stats",
           "record_launch",
           "jit_cache_sizes", "ref"]
