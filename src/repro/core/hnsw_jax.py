"""Device-side HNSW beam search — `lax.while_loop` over packed arrays.

TPU-native replacement for heap-based best-first search (DESIGN.md §2): the
candidate list is a fixed-size (ef,) sorted register file folded with
`jax.lax.top_k`; visited state is a dense (n,) mask updated by scatter.  One
loop iteration expands exactly one node: gather its ≤2M neighbours, batch
their distances (VPU/MXU), fold into the list.  Matches `HNSW.search` on
recall (tie-breaks aside) — asserted in tests/test_hnsw.py.

All shapes are static: (k, ef, max_iter) are trace-time constants, so the
same compiled artifact serves every query against a given graph.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

_INF = jnp.inf


@functools.partial(jax.jit, static_argnames=("k", "ef", "max_iter", "metric"))
def hnsw_search(vectors: jax.Array, ids: jax.Array, level0: jax.Array,
                entry: jax.Array, query: jax.Array, *, k: int, ef: int,
                max_iter: int | None = None, metric: str = "l2"
                ) -> Tuple[jax.Array, jax.Array]:
    """Single-query beam search on the level-0 graph.

    vectors : (V, d) global vector table
    ids     : (n,)  local slot -> global id (int32)
    level0  : (n, 2M) neighbour slots, -1 padded
    entry   : ()   entry slot
    query   : (d,)

    Returns (dists (k,), global_ids (k,)) ascending; unfilled = (inf, -1).
    """
    n = ids.shape[0]
    if max_iter is None:
        max_iter = 4 * ef + 16
    q = query.astype(jnp.float32)

    def dist_of(slots: jax.Array) -> jax.Array:
        g = ids[jnp.clip(slots, 0, n - 1)]
        v = vectors[g].astype(jnp.float32)
        if metric == "l2":
            diff = v - q[None, :]
            return jnp.sum(diff * diff, axis=-1)
        return -jnp.dot(v, q, precision=jax.lax.Precision.HIGHEST)

    # --- initial candidate list -------------------------------------------
    cand_s = jnp.full((ef,), -1, jnp.int32).at[0].set(entry.astype(jnp.int32))
    cand_d = jnp.full((ef,), _INF, jnp.float32).at[0].set(
        dist_of(entry[None].astype(jnp.int32))[0])
    expanded = jnp.zeros((ef,), jnp.bool_)
    visited = jnp.zeros((n,), jnp.bool_).at[entry].set(True)

    def cond(state):
        i, cand_d, cand_s, expanded, visited = state
        unexp = jnp.where(expanded | (cand_s < 0), _INF, cand_d)
        best_unexp = jnp.min(unexp)
        worst_kept = jnp.max(jnp.where(cand_s < 0, -_INF, cand_d))
        return (i < max_iter) & jnp.isfinite(best_unexp) & (
            best_unexp <= worst_kept)

    def body(state):
        i, cand_d, cand_s, expanded, visited = state
        unexp = jnp.where(expanded | (cand_s < 0), _INF, cand_d)
        pick = jnp.argmin(unexp)
        expanded = expanded.at[pick].set(True)
        node = cand_s[pick]

        nb = level0[jnp.clip(node, 0, n - 1)]                  # (2M,)
        valid = (nb >= 0) & ~visited[jnp.clip(nb, 0, n - 1)]
        nd = jnp.where(valid, dist_of(nb), _INF)
        visited = visited.at[jnp.clip(nb, 0, n - 1)].set(
            visited[jnp.clip(nb, 0, n - 1)] | (nb >= 0))

        # fold neighbours into the ef-list
        all_d = jnp.concatenate([cand_d, nd])
        all_s = jnp.concatenate([cand_s, jnp.where(valid, nb, -1)])
        all_e = jnp.concatenate([expanded, jnp.zeros_like(valid)])
        neg_top, pos = jax.lax.top_k(-all_d, ef)
        cand_d = -neg_top
        cand_s = all_s[pos]
        expanded = all_e[pos]
        return (i + 1, cand_d, cand_s, expanded, visited)

    _, cand_d, cand_s, _, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), cand_d, cand_s, expanded, visited))

    kk = min(k, ef)
    neg_top, pos = jax.lax.top_k(-cand_d, kk)
    out_d = -neg_top
    out_s = cand_s[pos]
    out_g = jnp.where(out_s >= 0, ids[jnp.clip(out_s, 0, n - 1)], -1)
    out_d = jnp.where(out_s >= 0, out_d, _INF)
    if kk < k:
        out_d = jnp.pad(out_d, (0, k - kk), constant_values=_INF)
        out_g = jnp.pad(out_g, (0, k - kk), constant_values=-1)
    return out_d, out_g.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "ef", "max_iter", "metric"))
def hnsw_search_filtered(vectors: jax.Array, ids: jax.Array,
                         level0: jax.Array, entry: jax.Array,
                         query: jax.Array, allowed: jax.Array, *, k: int,
                         ef: int, max_iter: int | None = None,
                         metric: str = "l2"
                         ) -> Tuple[jax.Array, jax.Array]:
    """Beam search that consults a candidate bitmap in-loop (the packed
    executor's ``filtered_graph`` strategy for boolean conjunctions).

    ``allowed`` : (V,) bool over GLOBAL ids — the composed membership mask
    of the other conjuncts (tombstones pre-composed by the caller).

    The traversal beam is *unfiltered* — disallowed nodes still route the
    walk, exactly like filtered-DiskANN-style search — while a separate
    (k,)-slot result file folds in allowed nodes only.  Returns
    (dists (k,), global_ids (k,)) ascending; unfilled = (inf, -1).
    """
    n = ids.shape[0]
    if max_iter is None:
        max_iter = 4 * ef + 16
    q = query.astype(jnp.float32)

    def dist_of(slots: jax.Array) -> jax.Array:
        g = ids[jnp.clip(slots, 0, n - 1)]
        v = vectors[g].astype(jnp.float32)
        if metric == "l2":
            diff = v - q[None, :]
            return jnp.sum(diff * diff, axis=-1)
        return -jnp.dot(v, q, precision=jax.lax.Precision.HIGHEST)

    def allowed_of(slots: jax.Array) -> jax.Array:
        return allowed[ids[jnp.clip(slots, 0, n - 1)]]

    entry_s = entry.astype(jnp.int32)
    d0 = dist_of(entry_s[None])[0]
    cand_s = jnp.full((ef,), -1, jnp.int32).at[0].set(entry_s)
    cand_d = jnp.full((ef,), _INF, jnp.float32).at[0].set(d0)
    expanded = jnp.zeros((ef,), jnp.bool_)
    visited = jnp.zeros((n,), jnp.bool_).at[entry_s].set(True)
    ok0 = allowed_of(entry_s[None])[0]
    res_d = jnp.full((k,), _INF, jnp.float32).at[0].set(
        jnp.where(ok0, d0, _INF))
    res_s = jnp.full((k,), -1, jnp.int32).at[0].set(
        jnp.where(ok0, entry_s, -1))

    def cond(state):
        i, cand_d, cand_s, expanded, visited, res_d, res_s = state
        unexp = jnp.where(expanded | (cand_s < 0), _INF, cand_d)
        best_unexp = jnp.min(unexp)
        worst_kept = jnp.max(jnp.where(cand_s < 0, -_INF, cand_d))
        return (i < max_iter) & jnp.isfinite(best_unexp) & (
            best_unexp <= worst_kept)

    def body(state):
        i, cand_d, cand_s, expanded, visited, res_d, res_s = state
        unexp = jnp.where(expanded | (cand_s < 0), _INF, cand_d)
        pick = jnp.argmin(unexp)
        expanded = expanded.at[pick].set(True)
        node = cand_s[pick]

        nb = level0[jnp.clip(node, 0, n - 1)]                  # (2M,)
        valid = (nb >= 0) & ~visited[jnp.clip(nb, 0, n - 1)]
        nd = jnp.where(valid, dist_of(nb), _INF)
        visited = visited.at[jnp.clip(nb, 0, n - 1)].set(
            visited[jnp.clip(nb, 0, n - 1)] | (nb >= 0))

        # traversal fold: unfiltered, so the beam crosses masked-out nodes
        all_d = jnp.concatenate([cand_d, nd])
        all_s = jnp.concatenate([cand_s, jnp.where(valid, nb, -1)])
        all_e = jnp.concatenate([expanded, jnp.zeros_like(valid)])
        neg_top, pos = jax.lax.top_k(-all_d, ef)
        cand_d = -neg_top
        cand_s = all_s[pos]
        expanded = all_e[pos]

        # result fold: allowed nodes only
        keep = valid & allowed_of(nb)
        rd = jnp.concatenate([res_d, jnp.where(keep, nd, _INF)])
        rs = jnp.concatenate([res_s, jnp.where(keep, nb, -1)])
        neg_top, pos = jax.lax.top_k(-rd, k)
        res_d = -neg_top
        res_s = rs[pos]
        return (i + 1, cand_d, cand_s, expanded, visited, res_d, res_s)

    _, _, _, _, _, res_d, res_s = jax.lax.while_loop(
        cond, body, (jnp.int32(0), cand_d, cand_s, expanded, visited,
                     res_d, res_s))
    out_g = jnp.where(res_s >= 0, ids[jnp.clip(res_s, 0, n - 1)], -1)
    out_d = jnp.where(res_s >= 0, res_d, _INF)
    return out_d, out_g.astype(jnp.int32)


def hnsw_search_batch(vectors, ids, level0, entry, queries, *, k, ef,
                      max_iter=None, metric="l2", allowed=None):
    """vmap over queries: (B, d) -> (B, k) dists + global ids.  With
    ``allowed`` (a (V,) bool bitmap over global ids) the beam consults the
    bitmap in-loop and returns allowed nodes only."""
    if allowed is None:
        fn = functools.partial(hnsw_search, k=k, ef=ef, max_iter=max_iter,
                               metric=metric)
        return jax.vmap(lambda q: fn(vectors, ids, level0, entry, q))(queries)
    fn = functools.partial(hnsw_search_filtered, k=k, ef=ef,
                           max_iter=max_iter, metric=metric)
    return jax.vmap(
        lambda q: fn(vectors, ids, level0, entry, q, allowed))(queries)


# --------------------------------------------------------------------- #
# fused multi-graph beam search (DESIGN.md §3): one launch per size
# bucket, vmapped over (graph, query) pairs on stacked matrices
# --------------------------------------------------------------------- #

def _check_beam_capacity(k: int, ef: int) -> None:
    """The beam's ef-list is the only result store: asking for more than
    ``ef`` results can only ever return (+inf, -1) padding past ef, so the
    executor's tombstone over-fetch must stay within this bound
    (DESIGN.md §3)."""
    if k > ef:
        raise ValueError(
            f"k={k} exceeds the beam's ef-list capacity ef={ef}: slots "
            "past ef can never be filled.  Clamp the over-fetch to ef (the "
            "executor does) or raise ef_search")


@functools.partial(jax.jit, static_argnames=("k", "ef", "max_iter",
                                             "metric"))
def hnsw_search_fused(vectors, ids, level0, entry, gidx, queries, *, k, ef,
                      max_iter=None, metric="l2"):
    """Beam search vmapped over (graph, query) PAIRS of one size bucket.

    ``ids``: (G, n_max) local-slot→global-id stacks (0-padded — padded
    slots are unreachable: the walk only enters a slot via the entry point
    or a neighbour edge, and padded slots have neither); ``level0``:
    (G, n_max, 2M); ``entry``: (G,); ``gidx``: (P,) graph index per pair;
    ``queries``: (P, d).  One launch serves every request against every
    graph state in the bucket — the per-state launch loop this replaces
    cost one trace + one dispatch per (state, filter) combination.
    """
    with jax.named_scope("vm/beam"):
        _check_beam_capacity(k, ef)

        def one(g, q):
            return hnsw_search(vectors, ids[g], level0[g], entry[g], q, k=k,
                               ef=ef, max_iter=max_iter, metric=metric)

        return jax.vmap(one)(gidx, queries)


@functools.partial(jax.jit, static_argnames=("k", "ef", "max_iter",
                                             "metric"))
def hnsw_search_fused_filtered(vectors, ids, level0, entry, masks, midx,
                               gidx, queries, *, k, ef, max_iter=None,
                               metric="l2"):
    """Filtered variant of ``hnsw_search_fused``: pair p searches graph
    ``gidx[p]`` under candidate bitmap ``masks[midx[p]]`` ((Mn, V) bool
    over global ids — one row per DISTINCT mask, so conjunction sources
    sharing a bitmap ship it once per batch, not once per pair)."""
    with jax.named_scope("vm/beam"):
        _check_beam_capacity(k, ef)

        def one(g, m, q):
            return hnsw_search_filtered(vectors, ids[g], level0[g], entry[g],
                                        q, masks[m], k=k, ef=ef,
                                        max_iter=max_iter, metric=metric)

        return jax.vmap(one)(gidx, midx, queries)
