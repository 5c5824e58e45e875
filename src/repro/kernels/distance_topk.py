"""Fused distance + running-top-k kernel (Pallas, TPU).

This is the hot path of the paper's skip-build strategy (§4.1): states whose
base set is below threshold T are searched brute-force.  On TPU the winning
schedule is *not* the paper's scalar CPU loop but a flash-attention-style
streaming reduction:

  grid = (Q/bq, N/bn) with the N dimension innermost ("arbitrary" semantics —
  sequential on TPU).  Each step computes a (bq, bn) distance tile on the MXU
  and folds it into a per-query running top-k held in VMEM scratch; only the
  final (bq, k) winners are written to HBM.

Versus materializing the full (Q, N) distance matrix this removes the O(Q·N)
HBM round-trip — the kernel is compute-bound for d ≥ ~64 instead of
memory-bound, which is what pushes the §Perf roofline fraction up.

The segmented variant serves many (query, id-set) pairs per launch via
owner-id masking, and its **descriptor mode** (DESIGN.md §3,
``distance_topk_descriptors``) additionally resolves the candidate rows
on device: ``(seg_start, seg_len, owner)`` triples expand against the
resident CSR ``base_ids`` inside the same executable, so frozen-base
candidate ids never ship from the host — only the query rows, the
planning integers, and the post-watermark delta tail do.  Where every
descriptor of a launch is one run of consecutive table rows (a label's
segment in a table laid out in segment order), its **in-place form**
reads those rows where they lie, block by block through scalar-prefetched
block indices, instead of gathering a copy first.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128
BLOCK_N = 128
# The running top-k carry and the kernels' output blocks are one full
# lane row wide, whatever k is: k ≤ LANES, columns past k stay (+inf, -1).
LANES = 128


def fold_topk(val_scr, idx_scr, dist, base, k: int) -> None:
    """Fold a (bq, bn) distance tile into the running top-k held in the
    (bq, LANES) scratch refs ``val_scr`` / ``idx_scr`` (ascending, with
    (+inf, -1) past k).  Tile column c carries candidate index
    ``base + c``.

    k rounds of: the row minimum over carry and tile, the lowest position
    holding it (every carry position comes before every tile position,
    as in ``concat([carry, tile])``), a lane-masked write into column i
    of the new carry, and masking that winner to +inf.  Only lane
    reductions, compares and selects, which Mosaic lowers; ``lax.top_k``
    and ``take_along_axis`` inside a kernel body it does not.  Ties go to
    the lower position exactly as ``lax.top_k`` over the concatenation
    breaks them, and a slot filled with +inf gets index -1 — so the
    result is the one the former ``top_k`` fold returned."""
    cv = val_scr[...]
    ci = idx_scr[...]
    c_pos = jax.lax.broadcasted_iota(jnp.int32, cv.shape, 1)
    t_pos = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    none = cv.shape[1] + dist.shape[1]          # above every position

    def one(i, carry):
        cv, tv, out_v, out_i = carry
        m = jnp.minimum(jnp.min(cv, axis=1, keepdims=True),
                        jnp.min(tv, axis=1, keepdims=True))     # (bq, 1)
        pc = jnp.min(jnp.where(cv == m, c_pos, none), axis=1, keepdims=True)
        pt = jnp.min(jnp.where(tv == m, t_pos, none), axis=1, keepdims=True)
        from_c = pc < none
        ic = jnp.sum(jnp.where(c_pos == pc, ci, 0), axis=1, keepdims=True)
        win = jnp.where(from_c, ic, base + pt)
        win = jnp.where(m < jnp.inf, win, -1)
        col = c_pos == i
        out_v = jnp.where(col, m, out_v)
        out_i = jnp.where(col, win, out_i)
        cv = jnp.where(from_c & (c_pos == pc), jnp.inf, cv)
        tv = jnp.where(~from_c & (t_pos == pt), jnp.inf, tv)
        return cv, tv, out_v, out_i

    _, _, out_v, out_i = jax.lax.fori_loop(
        0, k, one, (cv, dist, jnp.full_like(cv, jnp.inf),
                    jnp.full_like(ci, -1)))
    val_scr[...] = out_v
    idx_scr[...] = out_i


def topk_outputs(q: int, block_q: int) -> dict:
    """``pallas_call`` out_specs, out_shape and scratch_shapes shared by
    the streaming top-k kernels: a (block_q, LANES) running carry in VMEM
    and (q, LANES) value/index outputs, which the caller cuts to k."""
    spec = pl.BlockSpec((block_q, LANES), lambda i, j: (i, 0))
    return dict(
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((q, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((q, LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((block_q, LANES), jnp.float32),
                        pltpu.VMEM((block_q, LANES), jnp.int32)])


def _dist_tile(x, y, metric: str, accum: str):
    """(bq, d) × (bn, d) -> (bq, bn) distance tile.  ``accum="bf16"``
    rounds the operands to bf16 before the MXU contraction (half the
    VMEM, double the MXU rate) but keeps the accumulator and the norm
    epilogue in f32 — the bf16-accumulation contract DESIGN.md §6
    specifies and the tolerance tests bound.  ``accum="f32"`` contracts
    at full fp32 precision (``HIGHEST``), not the TPU's one-pass bf16
    default, so exact search stays exact on the chip."""
    if accum == "bf16":
        x = x.astype(jnp.bfloat16)
        y = y.astype(jnp.bfloat16)
        precision = None
    else:
        x = x.astype(jnp.float32)
        y = y.astype(jnp.float32)
        precision = jax.lax.Precision.HIGHEST
    xy = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    if metric == "l2":
        xf = x.astype(jnp.float32)
        yf = y.astype(jnp.float32)
        x2 = jnp.sum(xf * xf, axis=-1, keepdims=True)
        y2 = jnp.sum(yf * yf, axis=-1)[None, :]
        return jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)   # (bq, bn)
    return -xy


def _topk_kernel(x_ref, y_ref, val_out_ref, idx_out_ref,
                 val_scr, idx_scr, *, metric: str, k: int, block_n: int,
                 n_blocks: int, valid_n: int, accum: str):
    j = pl.program_id(1)

    # --- reset the running top-k at the start of each query row ------------
    @pl.when(j == 0)
    def _init():
        val_scr[...] = jnp.full_like(val_scr, jnp.inf)
        idx_scr[...] = jnp.full_like(idx_scr, -1)

    dist = _dist_tile(x_ref[...], y_ref[...], metric, accum)

    base = j * block_n
    col_idx = base + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    # Padded base rows (col >= valid_n) must never win the top-k.
    if valid_n < n_blocks * block_n:
        dist = jnp.where(col_idx < valid_n, dist, jnp.inf)

    fold_topk(val_scr, idx_scr, dist, base, k)

    # --- emit on the last tile of the row ----------------------------------
    @pl.when(j == n_blocks - 1)
    def _emit():
        val_out_ref[...] = val_scr[...]
        idx_out_ref[...] = idx_scr[...]


def _topk_seg_kernel(x_ref, y_ref, qseg_ref, cseg_ref, val_out_ref,
                     idx_out_ref, val_scr, idx_scr, *, metric: str, k: int,
                     block_n: int, n_blocks: int, valid_n: int, accum: str):
    """Segmented variant: row r may only take candidates c with
    cseg[c] == qseg[r], so one launch serves many (query, id-set) pairs."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        val_scr[...] = jnp.full_like(val_scr, jnp.inf)
        idx_scr[...] = jnp.full_like(idx_scr, -1)

    dist = _dist_tile(x_ref[...], y_ref[...], metric, accum)

    base = j * block_n
    col_idx = base + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    owner_q = qseg_ref[...]                        # (bq, 1)
    owner_c = cseg_ref[...]                        # (1, bn)
    match = owner_q == owner_c                     # segment membership
    if valid_n < n_blocks * block_n:
        match = match & (col_idx < valid_n)
    dist = jnp.where(match, dist, jnp.inf)

    fold_topk(val_scr, idx_scr, dist, base, k)

    @pl.when(j == n_blocks - 1)
    def _emit():
        val_out_ref[...] = val_scr[...]
        idx_out_ref[...] = idx_scr[...]


def _topk_inplace_kernel(blk_ref, own_ref, lo_ref, hi_ref, x_ref, y_ref,
                         dead_ref, qseg_ref, val_out_ref, idx_out_ref,
                         val_scr, idx_scr, *, metric: str, k: int,
                         block_n: int, n_blocks: int, accum: str):
    """In-place variant: column step j reads block ``blk[j]`` of the
    resident table itself and ranks its rows at positions [lo[j], hi[j])
    for the query rows of owner ``own[j]``, tombstones masked.  Padding
    steps (owner -3) repeat the last real block, so no DMA is issued,
    and do no work.  Indices are table positions."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        val_scr[...] = jnp.full_like(val_scr, jnp.inf)
        idx_scr[...] = jnp.full_like(idx_scr, -1)

    owner = own_ref[j]

    @pl.when(owner >= 0)
    def _scan():
        dist = _dist_tile(x_ref[...], y_ref[...], metric, accum)
        base = blk_ref[j] * block_n
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
        live = ((pos >= lo_ref[j]) & (pos < hi_ref[j])
                & jnp.logical_not(dead_ref[...]))
        match = (qseg_ref[...] == owner) & live
        fold_topk(val_scr, idx_scr, jnp.where(match, dist, jnp.inf), base, k)

    @pl.when(j == n_blocks - 1)
    def _emit():
        val_out_ref[...] = val_scr[...]
        idx_out_ref[...] = idx_scr[...]


def _inplace_pallas_call(vectors, deleted, x, qseg, cols, k, *, metric,
                         block_q, block_n, interpret, accum="f32"):
    """``pallas_call`` plumbing of the in-place scan: ``cols`` is the
    ``(blk, own, lo, hi)`` table of ``column_table``, prefetched to
    scalar memory, whose block indices steer the table's and the
    tombstone mask's ``BlockSpec``s."""
    q, d = x.shape
    n_blocks = int(cols[0].shape[0])
    assert q % block_q == 0 and k <= LANES, (q, block_q, k)
    kernel = functools.partial(_topk_inplace_kernel, metric=metric, k=k,
                               block_n=block_n, n_blocks=n_blocks,
                               accum=accum)
    outs = topk_outputs(q, block_q)
    spec = pl.BlockSpec((block_q, LANES), lambda i, j, *_: (i, 0))
    vals, idx = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(q // block_q, n_blocks),
            in_specs=[
                pl.BlockSpec((block_q, d), lambda i, j, *_: (i, 0)),
                pl.BlockSpec((block_n, d), lambda i, j, b, *_: (b[j], 0)),
                pl.BlockSpec((1, block_n), lambda i, j, b, *_: (0, b[j])),
                pl.BlockSpec((block_q, 1), lambda i, j, *_: (i, 0)),
            ],
            out_specs=[spec, spec],
            scratch_shapes=outs["scratch_shapes"]),
        out_shape=outs["out_shape"],
        interpret=interpret,
    )(*cols, x, vectors, deleted.reshape(1, -1), qseg)
    return vals[:, :k], idx[:, :k]


def _seg_pallas_call(x, y, qseg, cseg, k, *, metric, block_q, block_n,
                     interpret, valid_n, accum="f32"):
    """Shared pallas_call plumbing for the segmented kernel — used by the
    host-materialized path (``distance_topk_segmented``) and the
    descriptor-resolved path (``distance_topk_descriptors``)."""
    q, d = x.shape
    n, d2 = y.shape
    assert d == d2 and q % block_q == 0 and n % block_n == 0
    assert k <= LANES, k
    assert qseg.shape == (q, 1) and cseg.shape == (1, n)
    if valid_n is None:
        valid_n = n
    n_blocks = n // block_n
    grid = (q // block_q, n_blocks)
    kernel = functools.partial(_topk_seg_kernel, metric=metric, k=k,
                               block_n=block_n, n_blocks=n_blocks,
                               valid_n=valid_n, accum=accum)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        interpret=interpret,
        **topk_outputs(q, block_q),
    )(x, y, qseg, cseg)
    return vals[:, :k], idx[:, :k]


@functools.partial(jax.jit, static_argnames=("k", "metric", "block_q",
                                             "block_n", "interpret",
                                             "valid_n", "accum"))
def distance_topk_segmented(x: jax.Array, y: jax.Array, qseg: jax.Array,
                            cseg: jax.Array, k: int, *, metric: str = "l2",
                            block_q: int = BLOCK_Q, block_n: int = BLOCK_N,
                            interpret: bool = False,
                            valid_n: int | None = None,
                            accum: str = "f32"):
    """Segmented exact top-k.  x: (Q, d) queries, y: (N, d) concatenated
    candidate segments, qseg: (Q, 1) owner id per query row, cseg: (1, N)
    owner id per candidate row.  A candidate is eligible for a query iff the
    owner ids match; ineligible pairs never win (distance +inf, index -1).

    Padding convention (ops.py): padded query rows carry qseg -1 and padded
    candidate rows carry cseg -2, so they never match anything.
    """
    return _seg_pallas_call(x, y, qseg, cseg, k, metric=metric,
                            block_q=block_q, block_n=block_n,
                            interpret=interpret, valid_n=valid_n,
                            accum=accum)


# --------------------------------------------------------------------- #
# descriptor mode: candidates resolved against the device-resident CSR
# --------------------------------------------------------------------- #

def expand_descriptors(base_ids: jax.Array, starts: jax.Array,
                       lens: jax.Array, owners: jax.Array, n_desc: int):
    """Expand ``(seg_start, seg_len, owner)`` descriptor triples into a
    flat candidate-id + owner-id pair of length ``n_desc`` — entirely on
    device, against the resident CSR ``base_ids``.

    Descriptor d occupies flat slots [Σ lens[:d], Σ lens[:d+1]); slot i of
    descriptor d resolves to ``base_ids[starts[d] + i]`` with owner
    ``owners[d]``.  Slots past Σ lens (descriptor-region padding) get the
    unmatchable owner -3 and candidate position 0, so they can never win a
    segment's top-k.  Host→device traffic is the three (D,) int32 arrays —
    the candidate ids themselves never leave the device.
    """
    cum = jnp.cumsum(lens)                                   # (D,)
    slot = jnp.arange(n_desc, dtype=jnp.int32)
    d = jnp.searchsorted(cum, slot, side="right").astype(jnp.int32)
    dc = jnp.minimum(d, lens.shape[0] - 1)
    within = slot - (cum[dc] - lens[dc])
    valid = slot < cum[lens.shape[0] - 1]
    pos = jnp.where(valid, starts[dc] + within, 0)
    nb = max(int(base_ids.shape[0]), 1)
    cand = base_ids[jnp.clip(pos, 0, nb - 1)].astype(jnp.int32)
    own = jnp.where(valid, owners[dc], -3)
    return cand, own


def column_table(first: jax.Array, lens: jax.Array, owners: jax.Array,
                 n_blocks: int, block_n: int) -> tuple:
    """The column table ``(blk, own, lo, hi)``, each (n_blocks,), of an
    in-place scan whose descriptor d holds the table positions [first[d],
    first[d] + lens[d]): descriptor by descriptor, the aligned blocks of
    ``block_n`` rows that cover its run, with its owner and bounds.
    Columns past the last real block repeat that block with owner -3;
    ``n_blocks`` must hold the real ones."""
    lo_blk = first // block_n
    cnt = jnp.where(lens > 0, (first + lens - 1) // block_n - lo_blk + 1, 0)
    cum = jnp.cumsum(cnt)
    col = jnp.arange(n_blocks, dtype=jnp.int32)
    c = jnp.minimum(col, cum[-1] - 1)
    d = jnp.sum(cum[None, :] <= c[:, None], axis=1, dtype=jnp.int32)
    blk = lo_blk[d] + c - (cum[d] - cnt[d])
    own = jnp.where(col < cum[-1], owners[d], -3)
    return blk, own, first[d], first[d] + lens[d]


def packed_int_sizes(key: tuple) -> tuple:
    """Lengths of the int32 buffer's parts for a descriptor launch with
    bucket key ``(qp, n_desc, tr, ts, dp, d)``, in order: qseg (qp),
    descriptor starts, lens and owners (dp each), the resident tail's
    ids and owners (tr each) and the shipped tail's (ts each).  The
    float32 buffer is (qp + ts, d): the query rows, then the shipped
    tail's rows.  ``ops.pad_descriptor_batch`` packs this layout and
    ``unpack_descriptor_batch`` slices it."""
    qp, _, tr, ts, dp, _ = key
    return (qp, dp, dp, dp, tr, tr, ts, ts)


def unpack_descriptor_batch(floats: jax.Array, ints: jax.Array,
                            key: tuple) -> tuple:
    """The inputs of a descriptor launch out of the two buffers one
    upload ships, sliced at the static offsets of its bucket key
    (``packed_int_sizes``).  Returns ``(x, qseg, starts, lens, owners,
    tail_res_ids, tail_res_owners, tail_ship_ids, tail_ship_owners,
    tail_ship_rows)``, in ``distance_topk_descriptors``'s order."""
    qp = key[0]
    ends = list(itertools.accumulate(packed_int_sizes(key)))
    parts = [ints[a:b] for a, b in zip([0] + ends[:-1], ends)]
    return (floats[:qp], parts[0].reshape(qp, 1), *parts[1:],
            floats[qp:])


@functools.partial(jax.jit, static_argnames=("k", "n_desc", "packed",
                                             "metric", "block_q", "block_n",
                                             "interpret", "accum", "impl",
                                             "inplace"))
def distance_topk_descriptors(vectors: jax.Array, base_ids: jax.Array,
                              deleted: jax.Array, x: jax.Array,
                              qseg: jax.Array, starts=None, lens=None,
                              owners=None, tail_res_ids=None,
                              tail_res_owners=None, tail_ship_ids=None,
                              tail_ship_owners=None, tail_ship_rows=None,
                              k: int = 0, *, n_desc: int,
                              packed: tuple | None = None,
                              metric: str = "l2",
                              block_q: int = BLOCK_Q,
                              block_n: int = BLOCK_N,
                              interpret: bool = False,
                              accum: str = "f32", impl: str = "pallas",
                              inplace: bool = False):
    """Segmented top-k whose candidate sets are *descriptors* into the
    device-resident CSR, not host-materialized id lists.

    Flat candidate layout (all regions 0 or a multiple of ``block_n``):

      [ descriptor region (n_desc) | resident tail | shipped tail ]

    * descriptor region — ``(starts, lens, owners)`` triples expanded
      against ``base_ids`` (frozen chain covers / scan unions);
    * resident tail — explicit candidate ids below the upload watermark
      (masked conjunction scans, pre-watermark delta); rows gathered from
      the resident ``vectors`` table;
    * shipped tail — ids at/past the watermark whose rows
      (``tail_ship_rows``) ship from the host per batch (post-freeze
      delta inserts, bounded by the compaction threshold).

    ``deleted`` is the resident tombstone mask: resident candidates that
    are tombstoned get the unmatchable owner -3 in-kernel; shipped-tail
    tombstones must be filtered host-side by the caller.

    The inputs come as the ten arrays, or packed: with ``packed`` (the
    launch's bucket key) ``x`` is the float buffer and ``qseg`` the int
    buffer of ``ops.pad_descriptor_batch``, sliced apart here
    (``unpack_descriptor_batch``), and the arrays between are left out.

    Returns ``(vals, gids)`` of shape (Q, k), one row per (padded) query
    row: distances ascending and the candidates' rows of ``vectors`` (the
    executor's resident table lies in position space, DESIGN.md §2, and
    maps them back to ids), with every
    unfilled slot (+inf, -1) — no flat-position indices escape, so
    callers never map back through a host candidate array, and nothing
    is left to fix up outside the program.  The top-k core runs at k
    rounded up to 8 and keeps the first k.

    ``impl="xla"`` swaps the Pallas core for the dense jnp segmented
    sweep (``segmented_dense_topk``) — the XLA-compiled twin used where
    Pallas cannot compile (this container's CPU backend); assembly,
    gathers, and gid mapping are shared, so the two differ only in the
    top-k schedule.

    ``inplace=True`` is the in-place form, for a launch with no tails
    whose every descriptor covers one run of consecutive table positions
    (``base_ids[s:s + l]`` = p, p + 1, ...): the scan reads the aligned
    blocks of ``vectors`` that cover each run where they lie
    (``column_table``), with no gathered copy, and the ids it returns
    are those positions.  Its grid has the bucketed ``n_desc / block_n``
    columns and two more per descriptor slot, which always hold the
    runs' blocks: a run of l rows straddles at most ceil(l / block_n) + 1
    aligned blocks.  Its XLA twin reads the same blocks by row index.
    """
    with jax.named_scope("vm/scan"):
        if packed is not None:
            (x, qseg, starts, lens, owners, tail_res_ids, tail_res_owners,
             tail_ship_ids, tail_ship_owners,
             tail_ship_rows) = unpack_descriptor_batch(x, qseg, packed)
        kp = -(-k // 8) * 8
        if inplace:
            return _scan_inplace(vectors, base_ids, deleted, x, qseg,
                                 starts, lens, owners, k, kp,
                                 n_desc // block_n + 2 * lens.shape[0],
                                 metric=metric,
                                 block_q=block_q, block_n=block_n,
                                 interpret=interpret, accum=accum,
                                 impl=impl)
        y, cseg, gid_flat = assemble_flat_candidates(
            vectors, base_ids, deleted, starts, lens, owners, tail_res_ids,
            tail_res_owners, tail_ship_ids, tail_ship_owners, tail_ship_rows,
            n_desc)
        n = int(y.shape[0])
        if impl == "xla":
            vals, idx = segmented_dense_topk(x, y, qseg[:, 0], cseg, kp,
                                             metric=metric)
        else:
            vals, idx = _seg_pallas_call(
                x, y, qseg, cseg.reshape(1, n), kp, metric=metric,
                block_q=block_q, block_n=block_n, interpret=interpret,
                valid_n=n, accum=accum)
        vals, idx = vals[:, :k], idx[:, :k]
        gids = jnp.where(idx >= 0, gid_flat[jnp.clip(idx, 0, n - 1)], -1)
        bad = (gids < 0) | ~jnp.isfinite(vals)
        return jnp.where(bad, jnp.inf, vals), jnp.where(bad, -1, gids)


def _scan_inplace(vectors, base_ids, deleted, x, qseg, starts, lens, owners,
                  k: int, kp: int, n_blocks: int, *, metric, block_q,
                  block_n, interpret, accum, impl):
    """The in-place form of ``distance_topk_descriptors``: each run's
    first position is ``base_ids`` at its CSR start, and the scan reads
    the table blocks of ``column_table``.  Returns (vals, positions)
    with (+inf, -1) in every unfilled slot."""
    nb = max(int(base_ids.shape[0]), 1)
    first = base_ids[jnp.clip(starts, 0, nb - 1)].astype(jnp.int32)
    cols = column_table(first, lens, owners, n_blocks, block_n)
    if impl == "xla":
        blk, own, lo, hi = cols
        pos = (blk[:, None] * block_n
               + jnp.arange(block_n, dtype=jnp.int32)).reshape(-1)
        at = jnp.minimum(pos, int(vectors.shape[0]) - 1)
        live = ((pos >= jnp.repeat(lo, block_n))
                & (pos < jnp.repeat(hi, block_n)) & ~deleted[at])
        cseg = jnp.where(live, jnp.repeat(own, block_n), -3)
        vals, idx = segmented_dense_topk(x, vectors[at], qseg[:, 0], cseg,
                                         kp, metric=metric)
        idx = jnp.where(idx >= 0, pos[jnp.clip(idx, 0, pos.shape[0] - 1)],
                        -1)
    else:
        vals, idx = _inplace_pallas_call(
            vectors, deleted, x, qseg, cols, kp, metric=metric,
            block_q=block_q, block_n=block_n, interpret=interpret,
            accum=accum)
    vals, idx = vals[:, :k], idx[:, :k]
    bad = (idx < 0) | ~jnp.isfinite(vals)
    return jnp.where(bad, jnp.inf, vals), jnp.where(bad, -1, idx)


def assemble_flat_candidates(vectors, base_ids, deleted, starts, lens,
                             owners, tail_res_ids, tail_res_owners,
                             tail_ship_ids, tail_ship_owners,
                             tail_ship_rows, n_desc: int):
    """Device-side assembly of the flat candidate layout shared by the
    fp32 descriptor kernel and the SQ8 segmented path: returns
    ``(y (N, d) rows, cseg (N,) owners, gid_flat (N,) global ids)`` with
    tombstoned resident candidates reassigned to the unmatchable owner
    -3.  Traced inside the callers' jits, so XLA fuses the expansion and
    gathers with the downstream kernel."""
    if n_desc:
        dcand, down = expand_descriptors(base_ids, starts, lens, owners,
                                         n_desc)
    else:
        dcand = jnp.empty((0,), jnp.int32)
        down = jnp.empty((0,), jnp.int32)
    cand_res = jnp.concatenate([dcand, tail_res_ids.astype(jnp.int32)])
    own_res = jnp.concatenate([down, tail_res_owners.astype(jnp.int32)])
    dn = int(deleted.shape[0])
    if dn and cand_res.shape[0]:
        dead = deleted[jnp.clip(cand_res, 0, dn - 1)]
        own_res = jnp.where(dead, -3, own_res)
    y_parts = []
    if cand_res.shape[0]:
        y_parts.append(vectors[cand_res])
    if tail_ship_rows.shape[0]:
        y_parts.append(tail_ship_rows)
    y = (jnp.concatenate(y_parts, axis=0) if len(y_parts) > 1
         else y_parts[0])
    cseg = jnp.concatenate([own_res, tail_ship_owners.astype(jnp.int32)])
    gid_flat = jnp.concatenate([cand_res, tail_ship_ids.astype(jnp.int32)])
    return y, cseg, gid_flat


def segmented_dense_topk(x: jax.Array, y: jax.Array, qseg: jax.Array,
                         owners: jax.Array, k: int, *, metric: str = "l2"):
    """Dense segmented top-k in plain jnp — the *shard-local* sweep of the
    distributed executor (DESIGN.md §5).

    Runs inside ``shard_map``, where a ``pallas_call`` grid over the
    ragged per-shard candidate pool buys nothing (the pool is already a
    bounded, bucketed slice of one shard): a single MXU matmul plus
    ``lax.top_k`` is the winning schedule, mirroring what ``sharded_topk``
    always did for the unconstrained case.

    ``x`` (Q, d) queries, ``y`` (C, d) candidate rows, ``qseg`` (Q,) owner
    id per query row, ``owners`` (C,) owner id per candidate (negative =
    unmatchable padding).  Returns (Q, k) ascending distances plus
    positions into ``y``; unfilled slots are (+inf, -1) — the same
    sentinel contract as ``ops.topk_numpy``.
    """
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    xy = jax.lax.dot_general(
        xf, yf, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    if metric == "l2":
        x2 = jnp.sum(xf * xf, axis=-1, keepdims=True)
        y2 = jnp.sum(yf * yf, axis=-1)[None, :]
        dist = jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)
    else:
        dist = -xy
    match = qseg[:, None] == owners[None, :]
    dist = jnp.where(match, dist, jnp.inf)
    kk = min(k, int(y.shape[0]))
    neg, idx = jax.lax.top_k(-dist, kk)
    vals = -neg
    bad = ~jnp.isfinite(vals)
    vals = jnp.where(bad, jnp.inf, vals)
    idx = jnp.where(bad, -1, idx)
    if kk < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - kk)),
                       constant_values=jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - kk)), constant_values=-1)
    return vals, idx


@functools.partial(jax.jit, static_argnames=("k", "metric", "block_q",
                                             "block_n", "interpret",
                                             "valid_n", "accum"))
def distance_topk(x: jax.Array, y: jax.Array, k: int, *, metric: str = "l2",
                  block_q: int = BLOCK_Q, block_n: int = BLOCK_N,
                  interpret: bool = False, valid_n: int | None = None,
                  accum: str = "f32"):
    """Exact top-k over the base set.  x: (Q, d), y: (N, d).

    Returns (values, indices) of shape (Q, k); distances ascending.
    Q % block_q == 0, N % block_n == 0, k <= LANES (ops.py pads).
    ``valid_n``: logical base count; rows >= valid_n are padding and are
    masked to +inf in-kernel.
    """
    q, d = x.shape
    n, d2 = y.shape
    assert d == d2 and q % block_q == 0 and n % block_n == 0
    assert k <= LANES, k
    if valid_n is None:
        valid_n = n
    n_blocks = n // block_n
    grid = (q // block_q, n_blocks)
    kernel = functools.partial(_topk_kernel, metric=metric, k=k,
                               block_n=block_n, n_blocks=n_blocks,
                               valid_n=valid_n, accum=accum)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        interpret=interpret,
        **topk_outputs(q, block_q),
    )(x, y)
    return vals[:, :k], idx[:, :k]
