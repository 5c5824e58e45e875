"""Pallas kernel sweeps vs the pure-jnp oracles (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import pairwise_sqdist_ref, topk_ref

SHAPES = [
    (1, 7, 3, 5),
    (5, 300, 64, 10),
    (33, 1000, 100, 17),
    (128, 512, 384, 10),
    (128, 128, 128, 128),
    (2, 5, 1536, 3),
    (17, 259, 768, 32),
]


@pytest.mark.parametrize("q,n,d,k", SHAPES)
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_topk_matches_ref(q, n, d, k, metric):
    rng = np.random.default_rng(q * 1000 + n)
    x = jnp.asarray(rng.standard_normal((q, d)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    v, i = ops.topk(x, y, k, metric=metric)
    valid = min(k, n)
    rv, ri = topk_ref(x, y, valid, metric=metric)
    np.testing.assert_allclose(np.asarray(v)[:, :valid], np.asarray(rv),
                               atol=2e-4, rtol=1e-4)
    if k > n:
        assert np.all(np.asarray(i)[:, n:] == -1)
    # returned indices must achieve the returned distances
    iv = np.asarray(i)[:, :valid]
    dv = np.asarray(v)[:, :valid]
    yv = np.asarray(y)
    xv = np.asarray(x)
    for qi in range(min(q, 4)):
        for kk in range(valid):
            diff = xv[qi] - yv[iv[qi, kk]]
            d_true = float(diff @ diff) if metric == "l2" else \
                -float(xv[qi] @ yv[iv[qi, kk]])
            assert abs(d_true - dv[qi, kk]) < 2e-3 + 1e-4 * abs(d_true)


@pytest.mark.parametrize("q,n,d,k", SHAPES[:5])
def test_pairwise_matches_ref(q, n, d, k):
    rng = np.random.default_rng(q + n)
    x = jnp.asarray(rng.standard_normal((q, d)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    got = ops.pairwise_sqdist(x, y)
    want = pairwise_sqdist_ref(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_dtypes(dtype):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((8, 64)), dtype)
    y = jnp.asarray(rng.standard_normal((200, 64)), dtype)
    v, i = ops.topk(x, y, 5)
    rv, ri = topk_ref(x, y, 5)
    # bf16 inputs: compare index overlap (distances are low-precision)
    overlap = np.mean([
        len(set(np.asarray(i)[r].tolist())
            & set(np.asarray(ri)[r].tolist())) / 5 for r in range(8)])
    assert overlap >= 0.8


# --------------------------------------------------------------------- #
# segmented path: one launch, many (query, id-set) pairs
# --------------------------------------------------------------------- #

def _random_segments(rng, sizes, d):
    """Concatenated candidate segments + per-row owner ids."""
    y = rng.standard_normal((sum(sizes), d)).astype(np.float32)
    cseg = np.concatenate([np.full(s, o, np.int32)
                           for o, s in enumerate(sizes)]) if sizes else \
        np.empty(0, np.int32)
    return y, cseg


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_topk_segmented_matches_per_segment_topk(metric):
    """Parity with topk_numpy run per segment — including a segment smaller
    than k and candidate counts off the 128-lane boundary."""
    rng = np.random.default_rng(0)
    sizes = [40, 3, 216]                      # total 259: crosses lane pad
    d, k = 32, 9
    y, cseg = _random_segments(rng, sizes, d)
    qseg = np.array([0, 1, 2, 0, 2], np.int32)
    x = rng.standard_normal((len(qseg), d)).astype(np.float32)
    v, i = ops.topk_segmented(jnp.asarray(x), jnp.asarray(y), qseg, cseg, k,
                              metric=metric)
    v, i = np.asarray(v), np.asarray(i)
    rv, ri = ops.topk_segmented_numpy(x, y, qseg, cseg, k, metric=metric)
    assert np.array_equal(i, ri)
    np.testing.assert_allclose(v[i >= 0], rv[ri >= 0], atol=2e-4, rtol=1e-4)
    # per-segment cross-check against the dense oracle
    for r, owner in enumerate(qseg):
        cols = np.nonzero(cseg == owner)[0]
        dv, di = ops.topk_numpy(x[r:r + 1], y[cols], min(k, len(cols)),
                                metric=metric)
        valid = di[0] >= 0
        assert np.array_equal(i[r][i[r] >= 0], cols[di[0][valid]])
        # segment smaller than k -> trailing (-1, inf)
        if len(cols) < k:
            assert np.all(i[r][len(cols):] == -1)
            assert np.all(np.isinf(v[r][len(cols):]))


def test_topk_segmented_empty_and_unmatched_segments():
    rng = np.random.default_rng(1)
    y, cseg = _random_segments(rng, [17], 16)
    x = rng.standard_normal((3, 16)).astype(np.float32)
    # owner 5 has no candidates; owner -1 matches nothing by convention
    qseg = np.array([0, 5, -1], np.int32)
    v, i = ops.topk_segmented(jnp.asarray(x), jnp.asarray(y), qseg, cseg, 4)
    v, i = np.asarray(v), np.asarray(i)
    assert np.all(i[1] == -1) and np.all(np.isinf(v[1]))
    assert np.all(i[2] == -1) and np.all(np.isinf(v[2]))
    assert np.all(i[0] >= 0)


def test_topk_segmented_padded_lane_boundaries():
    """Candidates exactly at / just past the 128 lane: padding rows carry an
    unmatchable owner and must never be selected."""
    rng = np.random.default_rng(2)
    for n in (127, 128, 129, 256):
        y, cseg = _random_segments(rng, [n], 8)
        x = rng.standard_normal((1, 8)).astype(np.float32)
        qseg = np.zeros(1, np.int32)
        v, i = ops.topk_segmented(jnp.asarray(x), jnp.asarray(y), qseg,
                                  cseg, 10)
        i = np.asarray(i)
        assert np.all(i[0] >= 0) and np.all(i[0] < n)
        rv, ri = ops.topk_numpy(x, y, 10)
        assert np.array_equal(i[0], ri[0])


def test_topk_segmented_interleaved_owners():
    """Owner ids need not be contiguous runs — the mask is positional."""
    rng = np.random.default_rng(3)
    y = rng.standard_normal((50, 12)).astype(np.float32)
    cseg = (np.arange(50) % 2).astype(np.int32)
    x = rng.standard_normal((2, 12)).astype(np.float32)
    qseg = np.array([0, 1], np.int32)
    v, i = ops.topk_segmented(jnp.asarray(x), jnp.asarray(y), qseg, cseg, 5)
    i = np.asarray(i)
    assert np.all(i[0] % 2 == 0) and np.all(i[1] % 2 == 1)


def test_topk_numpy_matches_kernel():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((9, 48)).astype(np.float32)
    y = rng.standard_normal((333, 48)).astype(np.float32)
    nv, ni = ops.topk_numpy(x, y, 11)
    v, i = ops.topk(jnp.asarray(x), jnp.asarray(y), 11)
    np.testing.assert_allclose(nv, np.asarray(v), atol=2e-4, rtol=1e-4)


# --------------------------------------------------------------------- #
# the in-kernel running top-k fold
# --------------------------------------------------------------------- #

def _top_k_fold_ref(dist, k, block_n, tile_ids):
    """The fold the kernels used to run, tile after tile: ``lax.top_k``
    over ``concat([carry, tile])`` (ties to the lower position)."""
    q = dist.shape[0]
    cv = jnp.full((q, k), jnp.inf, jnp.float32)
    ci = jnp.full((q, k), -1, jnp.int32)
    for j in range(dist.shape[1] // block_n):
        sl = slice(j * block_n, (j + 1) * block_n)
        all_v = jnp.concatenate([cv, jnp.asarray(dist[:, sl])], axis=1)
        all_i = jnp.concatenate([ci, jnp.asarray(tile_ids[:, sl])], axis=1)
        neg, pos = jax.lax.top_k(-all_v, k)
        cv, ci = -neg, jnp.take_along_axis(all_i, pos, axis=1)
    return np.asarray(cv), np.asarray(ci)


@pytest.mark.parametrize("k", [1, 5, 13, 40, 128])
@pytest.mark.parametrize("segmented", [False, True],
                         ids=["plain", "segmented"])
def test_fold_matches_lax_top_k_with_ties(segmented, k):
    """0/1 vectors give a handful of distinct distances, so nearly every
    pick is a tie; k off the multiples of 8, up to the full 128 lanes,
    and (segmented) rows with fewer than k candidates."""
    from repro.kernels.distance_topk import (distance_topk,
                                             distance_topk_segmented)
    rng = np.random.default_rng(k)
    q, n, d, block_n = 8, 512, 4, 128
    x = rng.integers(0, 2, (q, d)).astype(np.float32)
    y = rng.integers(0, 2, (n, d)).astype(np.float32)
    dist = ((x * x).sum(1, keepdims=True) + (y * y).sum(1)[None, :]
            - 2.0 * x @ y.T)
    cols = np.broadcast_to(np.arange(n, dtype=np.int32), (q, n))
    if segmented:
        qseg = rng.integers(0, 3, (q, 1)).astype(np.int32)
        cseg = rng.integers(0, 3, (1, n)).astype(np.int32)
        cseg[0, :400] = 7                     # rows 0..2 match < 112 cands
        match = qseg == cseg
        v, i = distance_topk_segmented(
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(qseg),
            jnp.asarray(cseg), k, block_q=q, block_n=block_n,
            interpret=True)
        rv, ri = _top_k_fold_ref(np.where(match, dist, np.inf), k, block_n,
                                 np.where(match, cols, -1))
    else:
        valid_n = n - 37                      # padded tail rows never win
        v, i = distance_topk(jnp.asarray(x), jnp.asarray(y), k,
                             block_q=q, block_n=block_n, interpret=True,
                             valid_n=valid_n)
        rv, ri = _top_k_fold_ref(np.where(cols < valid_n, dist, np.inf), k,
                                 block_n, cols)
    assert np.array_equal(np.asarray(i), ri)
    assert np.array_equal(np.asarray(v), rv)
