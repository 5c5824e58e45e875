"""SQ8 quantized distance + top-k (beyond-paper, §Perf-Search).

Scalar quantization (per-vector symmetric int8) halves-to-quarters the HBM
bytes of the brute-force scan — the binding term of the search roofline
once the fused kernel removes the distance-matrix round-trip.  Exactness is
restored by an fp32 rerank of an over-fetched candidate set plus a
per-batch **certificate** (below); the paper's index stores raw fp32 and is
purely memory-bound at large N.

Distance identity used (L2):
    ‖x−y‖² = ‖x‖² + ‖y‖² − 2·sx·sy·(x_q·y_q)
with x_q,y_q int8 and the int32 MXU dot (exact for d ≤ 2^15: |dot| ≤
d·127² < 2³¹); ‖·‖² kept fp32 exactly, so the only approximation error is
the cross-term quantization noise.

Exactness certificate (DESIGN.md §6): with x = sx·x_q + e_x, |e_x,i| ≤
sx/2 (symmetric rounding, no clipping by construction of the scale), the
quantized estimate D̂ satisfies

    |D − D̂| ≤ ε(x,c) = sx·sy_c·(‖x_q‖₁ + ‖y_q,c‖₁ + d/2).

The scan keeps the top-kq by D̂; any excluded candidate therefore has
D ≥ D̂ − ε ≥ q_kq − ε_max, where q_kq is the kq-th kept quantized distance
and ε_max bounds ε over the query's live candidates.  If the k-th exact
reranked distance D_k < q_kq − ε_max, no excluded candidate can beat the
reranked winners and the batch's result equals the fp32 scan's.  Otherwise
the executor escalates the batch to the fp32 descriptor path — so
``quantize="sq8"`` is a pure bandwidth optimisation, never a recall trade.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .distance_topk import (LANES, expand_descriptors, fold_topk,
                            topk_outputs, unpack_descriptor_batch)
from .tuning import (SQ8_DIM_CAP, default_impl, default_interpret,
                     select_tiles)

f32 = jnp.float32

BLOCK_Q = 128
BLOCK_N = 128

# Above this k the overfetch factor (128-lane scratch / k) drops below 2
# and the quantized scan stops paying for its rerank tail.
SQ8_MAX_K = 64


def sq8_supported(k: int, dim: int, metric: str = "l2") -> bool:
    """Eligibility gate for the SQ8 scan path.  The executor falls back to
    the fp32 scan (recording the reason in ``sq8_stats``) rather than
    raising: L2 only (the certificate bound is an L2 identity), dim within
    the int8 tile budget, and k small enough that the 128-lane scratch
    still buys an overfetch factor ≥ 2."""
    return metric == "l2" and int(dim) <= SQ8_DIM_CAP and int(k) <= SQ8_MAX_K


def quantize_sq8(x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-row symmetric int8: returns (q int8, scale f32 (rows,1),
    sqnorm f32 (rows,1) of the ORIGINAL vectors)."""
    xf = x.astype(f32)
    scale = jnp.max(jnp.abs(xf), axis=1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    sq = jnp.sum(xf * xf, axis=1, keepdims=True)
    return q, scale, sq


def quantize_sq8_ext(x: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``quantize_sq8`` plus the L1 norm of the QUANTIZED codes
    (f32 (rows,1)) — the per-vector term of the certificate bound.  This is
    what ``PackedRuntime.to_device`` stores as the resident quantized
    table."""
    q, scale, sq = quantize_sq8(x)
    l1 = jnp.sum(jnp.abs(q.astype(jnp.int32)), axis=1,
                 keepdims=True).astype(f32)
    return q, scale, sq, l1


def _qtopk_kernel(xq_ref, sx_ref, x2_ref, yq_ref, sy_ref, y2_ref,
                  val_out_ref, idx_out_ref, val_scr, idx_scr, *,
                  k: int, block_n: int, n_blocks: int, valid_n: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        val_scr[...] = jnp.full_like(val_scr, jnp.inf)
        idx_scr[...] = jnp.full_like(idx_scr, -1)

    xq = xq_ref[...]                                  # (bq, d) int8
    yq = yq_ref[...]                                  # (bn, d) int8
    dot = jax.lax.dot_general(
        xq, yq, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32).astype(f32)  # (bq, bn)
    cross = dot * sx_ref[...] * sy_ref[...].reshape(1, -1)
    dist = x2_ref[...] + y2_ref[...].reshape(1, -1) - 2.0 * cross
    dist = jnp.maximum(dist, 0.0)

    base = j * block_n
    col = base + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    if valid_n < n_blocks * block_n:
        dist = jnp.where(col < valid_n, dist, jnp.inf)

    fold_topk(val_scr, idx_scr, dist, base, k)

    @pl.when(j == n_blocks - 1)
    def _emit():
        val_out_ref[...] = val_scr[...]
        idx_out_ref[...] = idx_scr[...]


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "interpret", "valid_n"))
def quantized_topk(xq, sx, x2, yq, sy, y2, k: int, *,
                   block_q: int = BLOCK_Q, block_n: int = BLOCK_N,
                   interpret: bool = False, valid_n: int | None = None):
    q, d = xq.shape
    n = yq.shape[0]
    assert q % block_q == 0 and n % block_n == 0 and k <= LANES
    if valid_n is None:
        valid_n = n
    n_blocks = n // block_n
    kernel = functools.partial(_qtopk_kernel, k=k, block_n=block_n,
                               n_blocks=n_blocks, valid_n=valid_n)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(q // block_q, n_blocks),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
        ],
        interpret=interpret,
        **topk_outputs(q, block_q),
    )(xq, sx, x2, yq, sy, y2)
    return vals[:, :k], idx[:, :k]


def _qtopk_seg_kernel(xq_ref, sx_ref, x2_ref, yq_ref, sy_ref, y2_ref,
                      qseg_ref, cseg_ref, val_out_ref, idx_out_ref,
                      val_scr, idx_scr, *, k: int, block_n: int,
                      n_blocks: int, valid_n: int):
    """Segmented variant of the SQ8 scan: row r may only take candidates c
    with cseg[c] == qseg[r] — one quantized launch serving every
    (query, id-set) pair in the batch, mirroring ``_topk_seg_kernel``."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        val_scr[...] = jnp.full_like(val_scr, jnp.inf)
        idx_scr[...] = jnp.full_like(idx_scr, -1)

    xq = xq_ref[...]                                  # (bq, d) int8
    yq = yq_ref[...]                                  # (bn, d) int8
    dot = jax.lax.dot_general(
        xq, yq, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32).astype(f32)  # (bq, bn)
    cross = dot * sx_ref[...] * sy_ref[...].reshape(1, -1)
    dist = x2_ref[...] + y2_ref[...].reshape(1, -1) - 2.0 * cross
    dist = jnp.maximum(dist, 0.0)

    base = j * block_n
    col = base + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    match = qseg_ref[...] == cseg_ref[...]            # (bq, bn) membership
    if valid_n < n_blocks * block_n:
        match = match & (col < valid_n)
    dist = jnp.where(match, dist, jnp.inf)

    fold_topk(val_scr, idx_scr, dist, base, k)

    @pl.when(j == n_blocks - 1)
    def _emit():
        val_out_ref[...] = val_scr[...]
        idx_out_ref[...] = idx_scr[...]


def _quantized_topk_segmented(xq, sx, x2, yq, sy, y2, qseg, cseg, k: int, *,
                              block_q: int = BLOCK_Q,
                              block_n: int = BLOCK_N,
                              interpret: bool = False,
                              valid_n: int | None = None):
    """Segmented SQ8 scan (traced inside ``topk_sq8_segmented_desc``).
    qseg: (Q, 1) owner per query row, cseg: (1, N) owner per candidate."""
    q, d = xq.shape
    n = yq.shape[0]
    assert q % block_q == 0 and n % block_n == 0 and k <= LANES
    if valid_n is None:
        valid_n = n
    n_blocks = n // block_n
    kernel = functools.partial(_qtopk_seg_kernel, k=k, block_n=block_n,
                               n_blocks=n_blocks, valid_n=valid_n)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(q // block_q, n_blocks),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        interpret=interpret,
        **topk_outputs(q, block_q),
    )(xq, sx, x2, yq, sy, y2, qseg, cseg)
    return vals[:, :k], idx[:, :k]


def _sq8_dense_segmented(xq, sx, x2, yq, sy, y2, qseg_vec, cseg, k: int):
    """XLA twin of the segmented int8 scan: one code-matrix matmul +
    ``lax.top_k``, mirroring ``segmented_dense_topk`` for the quantized
    estimate.  The compiled path off-TPU.

    For d ≤ 1024 the int8×int8 dot runs as an f32 GEMM of the code
    matrices — every partial sum is an integer bounded by d·127² < 2²⁴,
    which f32 represents exactly, so the result is bit-identical to the
    int32 dot while hitting the BLAS/MXU fp32 path instead of XLA's slow
    scalar int32 matmul.  Past that bound the int32 dot is kept."""
    d = int(xq.shape[1])
    if d * 127 * 127 < 2 ** 24 and jax.default_backend() != "tpu":
        dot = jax.lax.dot_general(
            xq.astype(f32), yq.astype(f32), (((1,), (1,)), ((), ())),
            preferred_element_type=f32)
    else:
        dot = jax.lax.dot_general(
            xq, yq, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32).astype(f32)
    cross = dot * sx * sy.reshape(1, -1)
    dist = jnp.maximum(x2 + y2.reshape(1, -1) - 2.0 * cross, 0.0)
    match = qseg_vec[:, None] == cseg[None, :]
    dist = jnp.where(match, dist, jnp.inf)
    neg, idx = jax.lax.top_k(-dist, k)
    vals = -neg
    bad = ~jnp.isfinite(vals)
    return jnp.where(bad, jnp.inf, vals), jnp.where(bad, -1, idx)


@functools.partial(jax.jit, static_argnames=("k", "kq", "packed",
                                             "interpret", "impl"))
def _sq8_topk_descriptors(vectors, vq, vsc, vsq, vl1, base_ids, deleted,
                          floats, ints, k: int, kq: int, *, packed: tuple,
                          interpret: bool = False, impl: str = "pallas"):
    """Descriptor-resolved SQ8 scan + fp32 rerank + certificate: the
    quantized analogue of ``distance_topk_descriptors``, fed the same two
    packed buffers (``ops.pad_descriptor_batch``; ``packed`` is the
    launch's bucket key).

    The candidate codes come from the RESIDENT quantized table
    ``(vq, vsc, vsq, vl1)`` uploaded once by ``to_device`` — only the
    shipped delta tail is quantized in-trace — so the scan reads int8
    rows from HBM and the only fp32 row traffic is the (Q, kq, d) rerank
    gather.  Returns ``(vals, gids, cert)``: exact reranked distances,
    global ids, and a per-query bool that is True iff the result provably
    equals the fp32 scan's (see module docstring); the executor escalates
    batches with any False row.  Rows are the padded query rows, unfilled
    slots (+inf, -1), padding rows certified."""
    with jax.named_scope("vm/sq8_scan"):
        (x, qseg, starts, lens, owners, tail_res_ids, tail_res_owners,
         tail_ship_ids, tail_ship_owners,
         tail_ship_rows) = unpack_descriptor_batch(floats, ints, packed)
        n_desc = packed[1]
        # --- assemble the flat candidate layout against the int8 table -----
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
        n_res = int(cand_res.shape[0])
        ts = int(tail_ship_rows.shape[0])

        yq_p, sy_p, y2_p, l1_p = [], [], [], []
        if n_res:
            yq_p.append(vq[cand_res])
            sy_p.append(vsc[cand_res])
            y2_p.append(vsq[cand_res])
            l1_p.append(vl1[cand_res])
        if ts:
            sq, ssc, ssq, sl1 = quantize_sq8_ext(tail_ship_rows)
            yq_p.append(sq)
            sy_p.append(ssc)
            y2_p.append(ssq)
            l1_p.append(sl1)
        cat = (lambda p: jnp.concatenate(p, axis=0) if len(p) > 1 else p[0])
        yq, sy, y2, yl1 = cat(yq_p), cat(sy_p), cat(y2_p), cat(l1_p)
        cseg = jnp.concatenate([own_res, tail_ship_owners.astype(jnp.int32)])
        gid_flat = jnp.concatenate([cand_res, tail_ship_ids.astype(jnp.int32)])
        n = n_res + ts
        qp, d = x.shape

        # --- int8 segmented scan: top-kq by quantized distance -------------
        xq, sx, x2, xl1 = quantize_sq8_ext(x)
        if impl == "xla":
            vals_q, idx = _sq8_dense_segmented(xq, sx, x2, yq, sy, y2,
                                               qseg[:, 0], cseg, kq)
        else:
            bq, bn = select_tiles(qp, n, d, itemsize=1, k=kq, divisor_n=n)
            vals_q, idx = _quantized_topk_segmented(
                xq, sx, x2, yq, sy, y2, qseg, cseg.reshape(1, n), kq,
                block_q=min(bq, qp), block_n=bn, interpret=interpret,
                valid_n=n)

        # --- exact fp32 rerank: gather only the (Q, kq, d) candidate rows --
        idxc = jnp.clip(idx, 0, n - 1)
        rowi = gid_flat[idxc]                    # resident gid == vectors row
        if n_res and ts:
            nv = max(int(vectors.shape[0]), 1)
            from_res = vectors[jnp.clip(rowi, 0, nv - 1)]
            from_ship = tail_ship_rows[jnp.clip(idxc - n_res, 0, ts - 1)]
            cand = jnp.where((idxc < n_res)[..., None], from_res, from_ship)
        elif ts:
            cand = tail_ship_rows[idxc]
        else:
            cand = vectors[rowi]
        xf = x.astype(f32)
        candf = cand.astype(f32)
        # same GEMM-form distance as the fp32 kernels, so certified results
        # are numerically interchangeable with the fp32 scan's
        xy = jnp.einsum("qd,qkd->qk", xf, candf,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=f32)
        c2 = jnp.sum(candf * candf, axis=-1)
        x2r = jnp.sum(xf * xf, axis=-1, keepdims=True)
        d2 = jnp.maximum(x2r + c2 - 2.0 * xy, 0.0)
        d2 = jnp.where(idx >= 0, d2, jnp.inf)
        neg, pos = jax.lax.top_k(-d2, k)
        fidx = jnp.take_along_axis(idx, pos, axis=1)
        gids = jnp.where(fidx >= 0, gid_flat[jnp.clip(fidx, 0, n - 1)], -1)
        vals = jnp.where(fidx >= 0, -neg, jnp.inf)

        # --- certificate: can any excluded candidate beat the top-k? -------
        live = cseg >= 0
        own = jnp.clip(cseg, 0, qp - 1)
        u = jnp.where(live, sy[:, 0], 0.0)
        t = jnp.where(live, sy[:, 0] * (yl1[:, 0] + d / 2.0), 0.0)
        umax = jnp.zeros((qp,), f32).at[own].max(u)
        tmax = jnp.zeros((qp,), f32).at[own].max(t)
        oq = jnp.clip(qseg[:, 0], 0, qp - 1)
        eps = sx[:, 0] * (xl1[:, 0] * umax[oq] + tmax[oq])
        qkq = vals_q[:, -1]                      # kq-th kept quantized dist
        dk = vals[:, k - 1]                      # k-th exact reranked dist
        # margin absorbs f32 rounding of the quantized estimate; a NaN or a
        # clamped-to-zero q_kq fails the comparison and escalates safely
        margin = eps + 1e-5 * (jnp.abs(qkq) + jnp.abs(dk)) + 1e-12
        cert = jnp.isposinf(qkq) | (dk < qkq - margin) | (qseg[:, 0] < 0)
        bad = (gids < 0) | ~jnp.isfinite(vals)
        return (jnp.where(bad, jnp.inf, vals), jnp.where(bad, -1, gids),
                cert)


def topk_sq8_segmented_desc(vectors, quant, base_ids, deleted, batch, key,
                            k: int, *, overfetch: int = 4,
                            interpret: bool | None = None,
                            impl: str | None = None):
    """Batched SQ8 executor path: ONE segmented quantized launch for every
    scan item in the batch.  ``quant`` is the resident int8 table
    ``(vq, vsc, vsq, vl1)`` from ``to_device``; ``batch`` the uploaded
    ``(floats, ints)`` buffers of ``ops.pad_descriptor_batch`` and
    ``key`` their bucket key, as ``ops.topk_segmented_desc`` takes them.
    ``k·overfetch`` beyond the 128-lane scratch budget raises like the
    unsegmented wrapper.  Returns padded-row device arrays ``(vals, gids,
    cert)`` — see ``_sq8_topk_descriptors``."""
    from .ops import _round_up, record_launch
    if interpret is None:
        interpret = default_interpret()
    if impl is None:
        impl = default_impl()
    kq = max(k * overfetch, k)
    if kq > 128:
        raise ValueError(
            f"k*overfetch={kq} exceeds the quantized kernel's 128-lane "
            f"scratch budget (k={k}, overfetch={overfetch}); lower k or "
            f"overfetch (the executor clamps overfetch to 128//k)")
    kqp = min(_round_up(kq, 8), 128)
    vq, vsc, vsq, vl1 = quant
    out = _sq8_topk_descriptors(
        vectors, vq, vsc, vsq, vl1, base_ids, deleted, *batch, k, kqp,
        packed=key, interpret=interpret, impl=impl)
    record_launch("sq8_scan", key + (k, kqp, impl))
    return out


# --------------------------------------------------------------------- #
# public wrapper: quantized scan + fp32 rerank
# --------------------------------------------------------------------- #

def topk_sq8_rerank(x: jax.Array, y: jax.Array, k: int, *,
                    overfetch: int = 4, interpret: bool | None = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Exact-quality top-k at int8 scan bandwidth: quantized top-(k·of)
    candidates, then fp32 rerank of the candidates only.

    HBM bytes: N·d (int8) + k·of·d (fp32) vs N·d·4 for the fp32 scan —
    ~4× less at N ≫ k·of.
    """
    from .ops import _pad_to, _round_up
    if interpret is None:
        interpret = default_interpret()
    qn, d = x.shape
    n = y.shape[0]
    kq = max(k * overfetch, k)
    if kq > 128:
        raise ValueError(
            f"k*overfetch={kq} exceeds the quantized kernel's 128-lane "
            f"scratch budget (k={k}, overfetch={overfetch}); lower k or "
            f"overfetch (the executor clamps overfetch to 128//k)")
    xq, sx, x2 = quantize_sq8(x)
    yq, sy, y2 = quantize_sq8(y)
    kqp = min(_round_up(kq, 8), 128)
    bq, bn = select_tiles(qn, n, d, itemsize=1, k=kqp)
    qp = _round_up(max(qn, 1), bq)
    np_ = _round_up(max(n, 1), bn)

    def pad2(t, rows):
        return jnp.pad(t, ((0, rows - t.shape[0]), (0, 0)))

    vals, idx = quantized_topk(
        pad2(xq, qp), pad2(sx, qp), pad2(x2, qp),
        pad2(yq, np_), pad2(sy, np_), pad2(y2, np_),
        kqp, block_q=bq, block_n=bn, interpret=interpret, valid_n=n)
    idx = idx[:qn, :kq]
    # fp32 rerank of the candidate set
    cand = y[jnp.clip(idx, 0, n - 1)].astype(f32)       # (Q, kq, d)
    diff = cand - x[:, None, :].astype(f32)
    d2 = jnp.sum(diff * diff, axis=-1)
    d2 = jnp.where(idx >= 0, d2, jnp.inf)
    neg, pos = jax.lax.top_k(-d2, k)
    return -neg, jnp.take_along_axis(idx, pos, axis=1)
