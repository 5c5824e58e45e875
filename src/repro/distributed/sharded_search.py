"""Distributed vector search — shard_map over the `data` mesh axis.

The VectorMaton serving story at pod scale (DESIGN.md §5): the packed
generation is row-sharded across the `data` axis AT UPLOAD TIME — vector
table, tombstone bitmap, and a **shard-local CSR** (each state's base-ID
segment re-grouped by owning shard, ids rebased to local row indices) —
and a warm query batch executes entirely device-resident:

  * each plan entry's predicate lowers to per-shard ``(seg_start,
    seg_len, owner)`` **descriptors** against the local CSR (frozen chain
    covers) or to a per-shard candidate tail cached on device keyed by
    ``(generation, predicate key, delta version)`` (bitmap compositions,
    residual-verified sets, resident delta ids) — no dense ``(N,)``
    membership mask is built or shipped on the warm path;
  * ALL of the batch's entries run through ONE ``shard_map`` launch per
    shape bucket: every shard expands its descriptors, gathers its rows,
    runs the dense segmented sweep, and the cross-shard top-k reduction
    folds on device (``ops.merge_topk_allgather``) — collective volume
    O(devices · Q · k · 8 bytes) per batch, negligible against the
    distance compute, which is why brute-force pattern-constrained
    search scales linearly in chips;
  * delta overflow keeps the §4 contract: qualified ids past the shard
    watermark (inserts pending compaction and re-shard) are brute-forced
    host-side and merged, so answers stay exact mid-churn.

``sharded_topk`` below is the raw numeric primitive (arbitrary ``N`` on
any mesh — the table pads to a shard multiple internally and pad rows can
never win); ``PackedRuntime.shard_descriptors = False`` forces the legacy
dense-mask path (one mask upload + one launch per entry), kept as the
bit-exactness parity oracle.
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.packed import ranges

f32 = jnp.float32

_EMPTY_I = np.empty(0, np.int64)


def sharded_topk(mesh: Mesh, queries: jax.Array, base: jax.Array, k: int,
                 *, metric: str = "l2", axis: str = "data",
                 valid_mask: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k of `queries` (Q, d) against row-sharded `base` (N, d).

    ``valid_mask`` (N,) bool — e.g. the pattern-qualified subset V_p of a
    VectorMaton state; invalid rows never win.  ``N`` may be arbitrary on
    any mesh: a non-divisible table is padded to a shard multiple and the
    pad rows are masked in-sweep.  Returns (dists (Q, k), global indices
    (Q, k)); unfilled slots — fewer than ``k`` qualifying rows — are the
    same ``(+inf, -1)`` sentinels ``ops.topk_numpy`` pads with, never a
    finite-looking pad id.
    """
    from ..kernels.distance_topk import segmented_dense_topk
    from ..kernels import ops

    n = int(base.shape[0])
    shards = mesh.shape[axis]
    local_n = max(1, -(-n // shards))
    n_pad = local_n * shards
    if n_pad != n:
        # a non-divisible table cannot already be row-sharded; pad with
        # zero rows (masked by global index below) and shard the result
        base = jnp.pad(jnp.asarray(base), ((0, n_pad - n), (0, 0)))
        if valid_mask is not None:
            valid_mask = jnp.pad(jnp.asarray(valid_mask), (0, n_pad - n))

    def local(q, b, m):
        # q: (Q, d) replicated; b: (local_n, d); m: (local_n, 1) or None
        shard_id = jax.lax.axis_index(axis)
        col_g = shard_id * local_n + jnp.arange(local_n, dtype=jnp.int32)
        valid = col_g < n
        if m is not None:
            valid = valid & m[:, 0]
        owners = jnp.where(valid, 0, -1)
        qseg = jnp.zeros(q.shape[0], jnp.int32)
        vals, idx = segmented_dense_topk(q, b, qseg, owners, k,
                                         metric=metric)
        gidx = jnp.where(idx >= 0, shard_id * local_n + idx, -1)
        return ops.merge_topk_allgather(vals, gidx, axis, k)

    mask_arg = (valid_mask[:, None] if valid_mask is not None else None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(), P(axis, None),
                                 (P(axis, None) if valid_mask is not None
                                  else None)),
                       out_specs=(P(), P()), check_vma=False)
    return fn(queries, base, mask_arg)


# ===================================================================== #
# sharded device residency (one per (generation, mesh, watermark))
# ===================================================================== #

@dataclass
class _EntrySpec:
    """Device-executable form of one plan entry against one residency.

    ``states``: frozen chain states whose covers run as per-shard CSR
    descriptors (zero upload).  ``ranges``: partial attribute-segment
    slices ``(pseudo_state, rank_lo, rank_hi)`` — a numeric Range leaf;
    the dispatcher intersects the global rank window with each shard's
    rank run to get per-shard descriptor columns (still zero upload).
    ``tails``: (shards, t_pad) local row ids resident on device (-1
    padding) — bitmap compositions, residual survivors, resident delta
    ids — uploaded once and cached.  ``extra``: qualified ids past the
    shard watermark, brute-forced host-side."""
    states: List[int]
    tails: Optional[jax.Array]
    t_pad: int
    extra: np.ndarray
    ranges: List[Tuple[int, int, int]] = field(default_factory=list)


class ShardedDeviceIndex:
    """Row-sharded residency of one ``PackedRuntime`` generation.

    Built once per (mesh, axis, watermark) by
    ``PackedRuntime.to_device_sharded``; holds the sharded vector table,
    the sharded tombstone bitmap, the shard-local CSR, and the
    per-predicate spec cache.  The watermark ``n`` freezes which rows are
    device-resident — later delta inserts overflow to the host brute
    force exactly like the single-chip upload watermark (DESIGN.md §4).
    """

    PRED_CACHE_MAX = 256
    TAILS_CACHE_MAX = 64

    def __init__(self, runtime, mesh: Mesh, axis: str = "data",
                 n: Optional[int] = None) -> None:
        from ..kernels import ops
        self.rt = runtime
        self.mesh = mesh
        self.axis = axis
        self.shards = int(mesh.shape[axis])
        n = int(n) if n is not None else len(runtime.vectors)
        self.n = n
        self.local_n = max(1, -(-n // self.shards))
        self.n_pad = self.local_n * self.shards
        d = runtime.vectors.shape[1]
        row_spec = NamedSharding(mesh, P(axis, None))
        vec = np.zeros((self.n_pad, d), np.float32)
        vec[:n] = runtime.vectors[:n]
        self.vectors = jax.device_put(jnp.asarray(vec), row_spec)
        dmask = np.zeros(self.n_pad, dtype=bool)
        if runtime.deleted:
            gone = [i for i in runtime.deleted if i < n]
            dmask[gone] = True
        self.deleted = jax.device_put(jnp.asarray(dmask),
                                      NamedSharding(mesh, P(axis)))
        self._del_seen = set(runtime.deleted)
        # resident int8 table (codes, scale, sqnorm, code-L1), sharded
        # like the fp32 rows: the SQ8 sweep gathers these per candidate
        # and only touches fp32 rows for the (Q, kq) rerank gather.  Pad
        # rows quantize to all-zero codes and are owner-masked anyway.
        self.quant = None
        if getattr(runtime, "quantize", "none") == "sq8":
            scale = (np.abs(vec).max(axis=1, keepdims=True)
                     .astype(np.float32) / 127.0 + 1e-12)
            codes = np.clip(np.rint(vec / scale), -127,
                            127).astype(np.int8)
            sqn = (vec * vec).sum(axis=1, keepdims=True,
                                  dtype=np.float32)
            l1 = np.abs(codes.astype(np.int32)).sum(
                axis=1, keepdims=True).astype(np.float32)
            self.quant = tuple(
                jax.device_put(jnp.asarray(a), row_spec)
                for a in (codes, scale.astype(np.float32), sqn, l1))
        # ---- shard-local CSR: per state, the segment's ids re-grouped by
        # owning shard and rebased to local row indices.  A chain cover on
        # shard s is then the descriptor (csr_ptr[s][u], length) per chain
        # state u — host-resolvable integers, never a mask.
        base_ids = np.asarray(runtime.base_ids, dtype=np.int64)
        # n_csr counts chain states PLUS the attribute pseudo-segments
        # appended at build time — both address the same shard-local CSR
        n_csr = runtime.n_csr
        state_of = np.empty(len(base_ids), dtype=np.int64)
        lens = runtime.base_hi - runtime.base_lo
        state_of[ranges(runtime.base_lo, lens)] = np.repeat(
            np.arange(n_csr, dtype=np.int64), lens)
        resident = base_ids < n
        ids_r, st_r = base_ids[resident], state_of[resident]
        owner = ids_r // self.local_n
        local = (ids_r % self.local_n).astype(np.int32)
        # shard-major, state-minor, original order within — one stable sort
        order = np.lexsort((np.arange(len(ids_r)), st_r, owner))
        per = np.bincount(owner * n_csr + st_r,
                          minlength=self.shards * n_csr
                          ).reshape(self.shards, n_csr)
        ptr = np.zeros((self.shards, n_csr + 1), np.int64)
        np.cumsum(per, axis=1, out=ptr[:, 1:])
        shard_len = ptr[:, -1]
        l_pad = ops.bucket(int(shard_len.max()) if len(ids_r) else 1, 8)
        csr = np.zeros((self.shards, l_pad), np.int32)
        sorted_local = local[order]
        off = 0
        for s in range(self.shards):
            ln = int(shard_len[s])
            csr[s, :ln] = sorted_local[off:off + ln]
            off += ln
        self.csr_ptr = ptr                      # host: descriptor lookup
        self.csr_local = jax.device_put(jnp.asarray(csr), row_spec)
        # base ids past the watermark (a sharded table older than the
        # generation's vector table): per-state host overflow, merged
        # with the delta extras at query time
        self._overflow: Dict[int, np.ndarray] = {}
        if not resident.all():
            ids_o, st_o = base_ids[~resident], state_of[~resident]
            for u in np.unique(st_o):
                self._overflow[int(u)] = ids_o[st_o == u]
        # ---- attribute pseudo-segments (DESIGN.md §9): a Range leaf is a
        # RANK window [a, b) of one value-sorted segment.  The lexsort
        # above is stable in original segment order, so within (shard,
        # state) the shard-local run preserves ascending global rank —
        # a global rank window is therefore CONTIGUOUS per shard, located
        # by binary search over each shard's rank run.  Non-resident
        # members keep their ranks so overflow respects the window too.
        self._seg_ranks: Dict[int, List[np.ndarray]] = {}
        self._rank_overflow: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for u in range(runtime.n_states, n_csr):
            lo, hi = int(runtime.base_lo[u]), int(runtime.base_hi[u])
            seg = base_ids[lo:hi]
            ranks = np.arange(hi - lo, dtype=np.int64)
            rm = seg < n
            ow = seg[rm] // self.local_n
            rr = ranks[rm]
            self._seg_ranks[u] = [rr[ow == s] for s in range(self.shards)]
            if not rm.all():
                self._rank_overflow[u] = (ranks[~rm], seg[~rm])
        # (predicate key, delta version) -> _EntrySpec, LRU + stale purge
        self._pred_cache: "OrderedDict[Tuple, _EntrySpec]" = OrderedDict()
        # batch-signature -> concatenated tails (warm waves re-use the
        # device-side concat instead of re-emitting it every wave)
        self._tails_cache: "OrderedDict[Tuple, Tuple]" = OrderedDict()

    # ------------------------------------------------------------------ #
    def sync_tombstones(self, deleted: set) -> None:
        """Fold deletes that landed after this residency was built into
        the resident bitmap — one scatter per batch that saw new deletes,
        not a mask re-upload."""
        if len(deleted) == len(self._del_seen):
            return
        new = [i for i in deleted - self._del_seen if i < self.n]
        if new:
            upd = self.deleted.at[jnp.asarray(new, jnp.int32)].set(True)
            self.deleted = jax.device_put(
                upd, NamedSharding(self.mesh, P(self.axis)))
        self._del_seen = set(deleted)

    # ------------------------------------------------------------------ #
    def entry_spec(self, entry, delta_version: int) -> _EntrySpec:
        """Cached lowering of one plan entry (DESIGN.md §5): purge
        version-stale entries, refresh recency on hit, evict LRU."""
        key = (entry.key, delta_version)
        spec = self._pred_cache.get(key)
        if spec is not None:
            self._pred_cache.move_to_end(key)
            return spec
        for stale in [kk for kk in self._pred_cache
                      if kk[1] != delta_version]:
            del self._pred_cache[stale]
        while len(self._pred_cache) >= self.PRED_CACHE_MAX:
            self._pred_cache.popitem(last=False)
        spec = self._build_spec(entry)
        self._pred_cache[key] = spec
        return spec

    def _build_spec(self, entry) -> _EntrySpec:
        n = self.n
        srcs = entry.sources
        if len(srcs) == 1 and srcs[0].strategy == "chain":
            # frozen chain cover -> descriptors; resident delta -> tail;
            # post-watermark delta (and overflow base ids) -> host extras.
            # Cover segments are disjoint (Lemma 4) and disjoint from the
            # delta, so the candidate pool carries no duplicates.
            s = srcs[0]
            states = list(s.seg_states)
            ranges = [(int(u), int(a), int(b))
                      for u, a, b in getattr(s, "attr_ranges", [])]
            delta = (np.asarray(s.delta_ids, np.int64)
                     if s.delta_ids is not None else _EMPTY_I)
            res = delta[delta < n]
            extras = [delta[delta >= n]]
            extras += [self._overflow[u] for u in states
                       if u in self._overflow]
            # partial attr windows: only overflow ids whose RANK falls
            # inside [a, b) qualify
            for u, a, b in ranges:
                if u in self._rank_overflow:
                    rk, ids_o = self._rank_overflow[u]
                    extras.append(ids_o[(rk >= a) & (rk < b)])
        else:
            # boolean composition / residual: the exact member set is
            # host-computed once (residual verification included) and the
            # resident half lives on device from then on — the dense mask
            # never ships
            mask = self.rt.entry_mask(entry)
            ids = np.nonzero(mask)[0].astype(np.int64)
            states = []
            ranges = []
            res = ids[ids < n]
            extras = [ids[ids >= n]]
        tails, t_pad = (self._upload_tails(res) if len(res)
                        else (None, 0))
        extra = (np.sort(np.concatenate(extras)) if any(len(x) for x in
                                                        extras)
                 else _EMPTY_I)
        return _EntrySpec(states=states, tails=tails, t_pad=t_pad,
                          extra=extra, ranges=ranges)

    def _upload_tails(self, ids: np.ndarray) -> Tuple[jax.Array, int]:
        """Group explicit resident candidate ids by owning shard, rebase
        to local rows, pad to a bucket, upload sharded.  Happens once per
        (predicate, delta version) — the warm path replays the resident
        array."""
        from ..kernels import ops
        owner = ids // self.local_n
        local = (ids % self.local_n).astype(np.int32)
        cnt = np.bincount(owner, minlength=self.shards)
        t_pad = ops.bucket(int(cnt.max()), 8)
        arr = np.full((self.shards, t_pad), -1, np.int32)
        order = np.argsort(owner, kind="stable")
        sorted_local = local[order]
        off = 0
        for s in range(self.shards):
            arr[s, :cnt[s]] = sorted_local[off:off + cnt[s]]
            off += int(cnt[s])
        tf = self.rt.traffic
        tf["shard_tail_bytes"] += int(arr.nbytes)
        tf["bytes_to_device"] += int(arr.nbytes)
        dev = jax.device_put(jnp.asarray(arr),
                             NamedSharding(self.mesh, P(self.axis, None)))
        return dev, t_pad

    def batch_tails(self, tail_parts: List[Tuple[object, jax.Array, int]],
                    t_pad_total: int, delta_version: int) -> jax.Array:
        """Concatenate the batch's per-entry resident tails along the
        candidate axis (device-side, sharding preserved) and pad to the
        bucket.  Cached per batch signature — the ordered predicate keys
        plus the delta version, which fully determine the concatenated id
        content (specs are rebuilt deterministically per (key, version));
        a steady-state wave replays one resident array with zero per-wave
        device ops.  Owner ids are NOT baked in: they depend on the
        batch's entry order and ship as planning integers per wave."""
        key = (tuple((ekey, int(arr.shape[1]))
                     for ekey, arr, _ in tail_parts),
               t_pad_total, delta_version)
        hit = self._tails_cache.get(key)
        if hit is not None:
            self._tails_cache.move_to_end(key)
            return hit
        for stale in [kk for kk in self._tails_cache
                      if kk[2] != delta_version]:
            del self._tails_cache[stale]    # dead: version can't hit again
        cat = (jnp.concatenate([arr for _, arr, _ in tail_parts], axis=1)
               if len(tail_parts) > 1 else tail_parts[0][1])
        t = int(cat.shape[1])
        if t < t_pad_total:
            cat = jnp.pad(cat, ((0, 0), (0, t_pad_total - t)),
                          constant_values=-1)
        cat = jax.device_put(
            cat, NamedSharding(self.mesh, P(self.axis, None)))
        while len(self._tails_cache) >= self.TAILS_CACHE_MAX:
            self._tails_cache.popitem(last=False)
        self._tails_cache[key] = cat
        return cat


# ===================================================================== #
# the bucketed sweep: ONE shard_map launch for a whole batch of entries
# ===================================================================== #

@functools.lru_cache(maxsize=128)
def _sweep_fn(mesh: Mesh, axis: str, n_desc: int, k: int, metric: str,
              local_n: int):
    """Build (and cache) the jitted shard_map sweep for one static shape
    class.  Dynamic dims (query rows, descriptor count, tail width) are
    bucketed by the caller, so steady-state serving replays a fixed set
    of compiled executables — the single-chip launch-cache discipline
    (DESIGN.md §3) applied to the distributed path."""
    from ..kernels.distance_topk import (expand_descriptors,
                                         segmented_dense_topk)
    from ..kernels import ops

    def local(q, qseg, dstart, dlen, downer, tails, towner, vecs, dele,
              csr):
        # q (Q, d) + qseg (Q,) + downer/towner replicated; dstart/dlen
        # (1, D) + tails (1, T) + csr (1, L) + vecs (local_n, d) + dele
        # (local_n,) are this shard's blocks.
        parts_c, parts_o = [], []
        if n_desc:
            cand_d, own_d = expand_descriptors(
                csr[0], dstart[0], dlen[0], downer, n_desc)
            parts_c.append(cand_d)
            parts_o.append(own_d)
        if int(tails.shape[1]):
            t1 = tails[0]
            parts_c.append(jnp.maximum(t1, 0))
            parts_o.append(jnp.where(t1 >= 0, towner, -3))
        cand = (jnp.concatenate(parts_c) if len(parts_c) > 1
                else parts_c[0])
        own = (jnp.concatenate(parts_o) if len(parts_o) > 1
               else parts_o[0])
        own = jnp.where(dele[cand], -3, own)
        y = vecs[cand]
        vals, idx = segmented_dense_topk(q, y, qseg, own, k, metric=metric)
        shard_id = jax.lax.axis_index(axis)
        nc = int(cand.shape[0])
        gid = jnp.where(
            idx >= 0,
            shard_id * local_n + cand[jnp.clip(idx, 0, nc - 1)], -1)
        return ops.merge_topk_allgather(vals, gid, axis, k)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(axis, None), P(axis, None), P(),
                  P(axis, None), P(), P(axis, None), P(axis),
                  P(axis, None)),
        out_specs=(P(), P()), check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=128)
def _sweep_fn_sq8(mesh: Mesh, axis: str, n_desc: int, k: int, kq: int,
                  metric: str, local_n: int):
    """Quantized twin of ``_sweep_fn``: each shard scans its int8 table
    for the top-kq quantized candidates, reranks ONLY those kq rows in
    fp32 (exact, GEMM form), and evaluates the per-shard exactness
    certificate (``kernels.quant`` module docstring).  The third output
    is the batch-global count of uncertified query rows (psum-reduced):
    zero means the merged result provably equals the fp32 sweep's; the
    caller escalates otherwise.  HBM candidate traffic drops from
    ``nc·d·4`` to ``nc·d + kq·d·4`` bytes per shard."""
    from ..kernels.distance_topk import expand_descriptors
    from ..kernels.quant import _sq8_dense_segmented, quantize_sq8_ext
    from ..kernels import ops

    def local(q, qseg, dstart, dlen, downer, tails, towner, vq, vsc, vsq,
              vl1, vecs, dele, csr):
        parts_c, parts_o = [], []
        if n_desc:
            cand_d, own_d = expand_descriptors(
                csr[0], dstart[0], dlen[0], downer, n_desc)
            parts_c.append(cand_d)
            parts_o.append(own_d)
        if int(tails.shape[1]):
            t1 = tails[0]
            parts_c.append(jnp.maximum(t1, 0))
            parts_o.append(jnp.where(t1 >= 0, towner, -3))
        cand = (jnp.concatenate(parts_c) if len(parts_c) > 1
                else parts_c[0])
        own = (jnp.concatenate(parts_o) if len(parts_o) > 1
               else parts_o[0])
        own = jnp.where(dele[cand], -3, own)
        nc = int(cand.shape[0])
        qp, d_dim = int(q.shape[0]), int(q.shape[1])

        xq, sx, x2, xl1 = quantize_sq8_ext(q)
        yq, sy, y2, yl1 = vq[cand], vsc[cand], vsq[cand], vl1[cand]
        kqe = min(kq, nc)
        vals_q, idx = _sq8_dense_segmented(xq, sx, x2, yq, sy, y2,
                                           qseg, own, kqe)
        # exact fp32 rerank of the shard-local winners only
        idxc = jnp.clip(idx, 0, nc - 1)
        rows = vecs[cand[idxc]]                       # (Q, kqe, d) fp32
        qf = q.astype(f32)
        xy = jnp.einsum("qd,qkd->qk", qf, rows,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=f32)
        c2 = jnp.sum(rows * rows, axis=-1)
        x2r = jnp.sum(qf * qf, axis=-1, keepdims=True)
        d2 = jnp.maximum(x2r + c2 - 2.0 * xy, 0.0)
        d2 = jnp.where(idx >= 0, d2, jnp.inf)
        ke = min(k, kqe)
        neg, pos = jax.lax.top_k(-d2, ke)
        fidx = jnp.take_along_axis(idx, pos, axis=1)
        vals = jnp.where(fidx >= 0, -neg, jnp.inf)
        shard_id = jax.lax.axis_index(axis)
        gid = jnp.where(
            fidx >= 0,
            shard_id * local_n + cand[jnp.clip(fidx, 0, nc - 1)], -1)
        if ke < k:
            vals = jnp.pad(vals, ((0, 0), (0, k - ke)),
                           constant_values=jnp.inf)
            gid = jnp.pad(gid, ((0, 0), (0, k - ke)),
                          constant_values=-1)

        if nc <= kq:
            # every shard-local candidate was reranked exactly
            cert = jnp.ones((qp,), bool)
        else:
            live = own >= 0
            ow = jnp.clip(own, 0, qp - 1)
            u = jnp.where(live, sy[:, 0], 0.0)
            t = jnp.where(live, sy[:, 0] * (yl1[:, 0] + d_dim / 2.0),
                          0.0)
            umax = jnp.zeros((qp,), f32).at[ow].max(u)
            tmax = jnp.zeros((qp,), f32).at[ow].max(t)
            oq = jnp.clip(qseg, 0, qp - 1)
            eps = sx[:, 0] * (xl1[:, 0] * umax[oq] + tmax[oq])
            qkq = vals_q[:, -1]
            dk = vals[:, k - 1]
            margin = eps + 1e-5 * (jnp.abs(qkq) + jnp.abs(dk)) + 1e-12
            cert = jnp.isposinf(qkq) | (dk < qkq - margin)
        mv, mi = ops.merge_topk_allgather(vals, gid, axis, k)
        bad = jax.lax.psum(jnp.sum((~cert).astype(jnp.int32)), axis)
        return mv, mi, bad

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(axis, None), P(axis, None), P(),
                  P(axis, None), P(), P(axis, None), P(axis, None),
                  P(axis, None), P(axis, None), P(axis, None), P(axis),
                  P(axis, None)),
        out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(fn)


# ===================================================================== #
# plan executor
# ===================================================================== #

def _extras_block(runtime, queries_np: np.ndarray, entry,
                  extra_ids: np.ndarray, metric: str):
    """Delta-overflow fold, shared by the descriptor and dense paths:
    drop tombstoned overflow ids and compute their host-side distance
    block against the entry's requests (the overflow is bounded by the
    compaction threshold, DESIGN.md §4)."""
    if len(extra_ids) and runtime.deleted:
        extra_ids = extra_ids[~np.isin(
            extra_ids, np.fromiter(runtime.deleted, dtype=np.int64))]
    if not len(extra_ids):
        return None, extra_ids
    ev = np.asarray(runtime.vectors[extra_ids], dtype=np.float32)
    qm = queries_np[entry.requests]
    if metric == "l2":
        ed = ((qm[:, None, :] - ev[None, :, :]) ** 2).sum(-1)
    else:
        ed = -(qm @ ev.T)
    return ed, extra_ids


def _merge_extras_row(dr: np.ndarray, ir: np.ndarray, ed_row: np.ndarray,
                      extra_ids: np.ndarray, k: int):
    """Stable-sort merge of one request's device winners with its host
    overflow block — the same tie-breaking as the single-chip merge, so
    the descriptor and dense paths stay bit-identical."""
    dr = np.concatenate([dr, ed_row.astype(np.float32)])
    ir = np.concatenate([ir, extra_ids])
    order = np.argsort(dr, kind="stable")[:k]
    return dr[order], ir[order]


@dataclass
class ShardedPending:
    """In-flight result of ``sharded_plan_dispatch`` (DESIGN.md §7).

    ``dv``/``gv`` are the sweep's (rows, k) outputs still on device —
    JAX async dispatch means the shard_map launch may still be running
    when dispatch returns.  ``sharded_plan_fetch`` crosses them to the
    host and runs the sentinel-filter + delta-overflow merge.  The SQ8
    certificate (``int(bad)``) is an inherent sync point and is resolved
    INSIDE dispatch — escalation to the fp32 sweep must happen before
    the launch set is final."""
    plan: object
    k: int
    metric: str
    queries_np: np.ndarray
    specs: List[_EntrySpec]
    out: List[Tuple[np.ndarray, np.ndarray]]
    dv: Optional[jax.Array] = None
    gv: Optional[jax.Array] = None
    fetched: bool = False


def sharded_plan_topk(mesh: Mesh, base, runtime, queries, plan, k: int, *,
                      metric: str = "l2", axis: str = "data"):
    """Execute a batched QueryPlan against the row-sharded generation.

    ``runtime`` is the PackedRuntime whose CSR the plan indexes into;
    ``plan`` comes from ``runtime.plan(...)`` / ``VectorMaton.plan(...)``.
    ``base`` fixes the shard watermark: an integer row count, a table
    whose length is the watermark (legacy call shape — only its length is
    read; the residency gathers rows from the runtime itself), or
    ``None`` to freeze the runtime's current table length on first use.
    Returns [(dists, ids)] aligned with the request batch; tombstoned IDs
    never win.

    Warm-path traffic per batch is the query matrix plus per-shard
    descriptor triples (``shard_descriptor_bytes``); per-predicate
    resident tails upload once into the spec cache
    (``shard_tail_bytes``); NO dense per-entry mask is built or shipped
    (``shard_mask_bytes`` stays 0 — the legacy path behind
    ``runtime.shard_descriptors = False`` is the parity oracle, which
    matches bit-for-bit up to exact-distance ties between DISTINCT ids:
    the descriptor pool is CSR-expansion order, the dense pool ascending
    row order, so only a tie at identical float distance can order
    differently).  All entries execute through ONE ``shard_map`` launch
    per shape bucket with the cross-shard top-k folded on device.

    Delta overflow (DESIGN.md §4): qualified ids past the shard
    watermark — inserts still sitting in the runtime's delta, pending
    compaction and re-shard — are brute-forced host-side against the
    runtime's live vector view and merged into each request's top-k.
    The delta is bounded by the compaction threshold, so this stays
    negligible against the sharded distance work, and answers remain
    exact mid-churn.
    """
    return sharded_plan_fetch(runtime, sharded_plan_dispatch(
        mesh, base, runtime, queries, plan, k, metric=metric, axis=axis))


def sharded_plan_dispatch(mesh: Mesh, base, runtime, queries, plan,
                          k: int, *, metric: str = "l2",
                          axis: str = "data") -> ShardedPending:
    """Launch the sharded sweep for a batched QueryPlan WITHOUT syncing
    on the merged top-k (DESIGN.md §7): staleness checks, entry
    lowering, descriptor/tail assembly and the single shard_map launch
    all run here; the (rows, k) outputs stay device futures inside the
    returned ``ShardedPending`` until ``sharded_plan_fetch``.  The
    legacy dense-mask oracle path and the SQ8 certificate check are
    synchronous inside dispatch (the certificate decides whether the
    fp32 sweep must also launch)."""
    from ..kernels import ops
    # same snapshot discipline as PackedRuntime.execute: a plan's CSR
    # offsets and delta id lists are only meaningful against the runtime
    # state that compiled them
    if plan.generation != runtime.generation:
        raise ValueError(
            f"stale plan: compiled against generation {plan.generation}, "
            f"sharded-executing on generation {runtime.generation} — "
            "snapshot the runtime once per batch")
    if plan.delta_version != runtime.delta.version:
        raise ValueError(
            f"stale plan: compiled at delta version {plan.delta_version}, "
            f"sharded-executing at {runtime.delta.version} — an insert "
            "landed between plan and execute; re-plan")
    queries_np = np.ascontiguousarray(np.asarray(queries),
                                      dtype=np.float32)
    out = [(np.empty(0, np.float32), np.empty(0, np.int64))
           ] * plan.n_requests
    if not plan.entries:
        return ShardedPending(plan=plan, k=k, metric=metric,
                              queries_np=queries_np, specs=[], out=out,
                              fetched=True)
    n_hint = None
    if base is not None:
        n_hint = (int(base) if isinstance(base, (int, np.integer))
                  else int(base.shape[0]))
    sh = runtime.to_device_sharded(mesh, axis=axis, n=n_hint)
    if not getattr(runtime, "shard_descriptors", True):
        out = _sharded_plan_topk_dense(mesh, sh, runtime, queries_np,
                                       plan, k, metric=metric, axis=axis)
        return ShardedPending(plan=plan, k=k, metric=metric,
                              queries_np=queries_np, specs=[], out=out,
                              fetched=True)
    sh.sync_tombstones(runtime.deleted)
    tf = runtime.traffic
    tf["shard_batches"] += 1
    d_dim = queries_np.shape[1]

    # ---- lower entries (cached) and assemble the single launch --------- #
    specs = [sh.entry_spec(e, plan.delta_version) for e in plan.entries]
    q_rows: List[int] = []
    q_owner: List[int] = []
    dstart_cols: List[np.ndarray] = []
    dlen_cols: List[np.ndarray] = []
    downer: List[int] = []
    tail_parts: List[Tuple[object, jax.Array, int, int]] = []
    for oi, (e, spec) in enumerate(zip(plan.entries, specs)):
        for u in spec.states:
            dstart_cols.append(sh.csr_ptr[:, u])
            dlen_cols.append(sh.csr_ptr[:, u + 1] - sh.csr_ptr[:, u])
            downer.append(oi)
        for u, a, b in spec.ranges:
            # partial attribute window: per shard, intersect the global
            # rank window [a, b) with the shard's ascending rank run —
            # the slice is contiguous in the shard-local CSR, so this is
            # still a pure descriptor (two binary searches, zero upload)
            runs = sh._seg_ranks[u]
            starts = np.empty(sh.shards, np.int64)
            lens = np.empty(sh.shards, np.int64)
            for si in range(sh.shards):
                lo_i = int(np.searchsorted(runs[si], a, side="left"))
                hi_i = int(np.searchsorted(runs[si], b, side="left"))
                starts[si] = sh.csr_ptr[si, u] + lo_i
                lens[si] = hi_i - lo_i
            dstart_cols.append(starts)
            dlen_cols.append(lens)
            downer.append(oi)
        if spec.tails is not None:
            tail_parts.append((e.key, spec.tails, oi, spec.t_pad))
        q_rows.extend(e.requests)
        q_owner.extend([oi] * len(e.requests))

    n_desc = 0
    d_pad = 0
    if downer:
        dlen_np = np.stack(dlen_cols, axis=1).astype(np.int32)
        dstart_np = np.stack(dstart_cols, axis=1).astype(np.int32)
        d_pad = ops.bucket(len(downer), 8)
        if d_pad > len(downer):
            pad = d_pad - len(downer)
            dlen_np = np.pad(dlen_np, ((0, 0), (0, pad)))
            dstart_np = np.pad(dstart_np, ((0, 0), (0, pad)))
        downer_np = np.full(d_pad, -3, np.int32)
        downer_np[:len(downer)] = downer
        n_desc = ops.bucket(int(dlen_np.sum(axis=1).max()), 8)
    else:
        dstart_np = np.zeros((sh.shards, 0), np.int32)
        dlen_np = np.zeros((sh.shards, 0), np.int32)
        downer_np = np.zeros(0, np.int32)

    # canonical order: the tails cache keys on this sequence, so rotating
    # predicate arrival orders must collapse to one concatenated array
    tail_parts.sort(key=lambda p: str(p[0]))
    t_total = sum(tp for _, _, _, tp in tail_parts)
    t_pad = ops.bucket(t_total, 8) if t_total else 0
    if tail_parts:
        towner_np = np.full(t_pad, -3, np.int32)
        off = 0
        for _, _, oi, tp in tail_parts:
            towner_np[off:off + tp] = oi
            off += tp
        tails_dev = sh.batch_tails(
            [(ekey, arr, tp) for ekey, arr, _, tp in tail_parts],
            t_pad, plan.delta_version)
    else:
        towner_np = np.zeros(0, np.int32)
        tails_dev = jax.device_put(
            jnp.zeros((sh.shards, 0), jnp.int32),
            NamedSharding(mesh, P(axis, None)))

    pending = ShardedPending(plan=plan, k=k, metric=metric,
                             queries_np=queries_np, specs=specs, out=out)
    if q_rows and n_desc + t_pad > 0:
        from ..kernels.quant import sq8_supported
        q_n = len(q_rows)
        q_pad = ops.bucket(q_n, 8)
        qmat = np.zeros((q_pad, d_dim), np.float32)
        qmat[:q_n] = queries_np[q_rows]
        qseg = np.full(q_pad, -1, np.int32)
        qseg[:q_n] = q_owner
        key = (q_pad, n_desc, d_pad, t_pad, k, metric, sh.shards,
               sh.local_n, d_dim)
        fp32_args = (jnp.asarray(qmat), jnp.asarray(qseg),
                     jnp.asarray(dstart_np), jnp.asarray(dlen_np),
                     jnp.asarray(downer_np), tails_dev,
                     jnp.asarray(towner_np), sh.vectors, sh.deleted,
                     sh.csr_local)
        dv = gv = None
        t_sweep = time.perf_counter()
        streaks = getattr(runtime, "_sq8_bad_streak", {})
        streak_out = (getattr(runtime, "sq8_escalate", True)
                      and streaks.get(None, 0)
                      >= getattr(runtime, "SQ8_MAX_STREAK", 3))
        if (sh.quant is not None and not streak_out
                and sq8_supported(k, d_dim, metric)):
            # quantized sweep + per-shard certificate; a failed batch
            # escalates to the fp32 sweep below (exactness contract),
            # and a streak of failures flips the runtime to fp32
            # outright (the single-chip path's policy for plain buckets)
            kq = min(128, max(k, k * max(1, min(4, 128 // max(k, 1)))))
            fn = _sweep_fn_sq8(mesh, axis, n_desc, k, kq, metric,
                               sh.local_n)
            dv, gv, bad = fn(*fp32_args[:7], *sh.quant, *fp32_args[7:])
            ops.record_launch("sq8_sharded_sweep", key + (kq,))
            runtime.sq8_stats["batches"] += 1
            if not getattr(runtime, "sq8_escalate", True):
                pass          # approximate point: trust the rerank
            elif int(bad):
                runtime.sq8_stats["escalations"] += 1
                streaks[None] = streaks.get(None, 0) + 1
                dv = gv = None
            else:
                runtime.sq8_stats["certified"] += 1
                streaks[None] = 0
        elif sh.quant is not None:
            runtime.sq8_stats["fallbacks"] += 1
        if dv is None:
            fn = _sweep_fn(mesh, axis, n_desc, k, metric, sh.local_n)
            dv, gv = fn(*fp32_args)
            ops.record_launch("sharded_sweep", key)
        planner = getattr(runtime, "planner", None)
        if planner is not None:
            # the sharded sweep is the distributed scan strategy: report
            # its observed cost (rows ranked × query rows) into the
            # index-owned cost model — folded at the next wave head, like
            # every other executor observation (DESIGN.md §11)
            planner.observe("scan",
                            (int(dlen_np.sum()) + t_total) * q_n,
                            (time.perf_counter() - t_sweep) * 1e3)
        desc_bytes = sh.shards * d_pad * 8 + d_pad * 4 + t_pad * 4
        tf["shard_descriptor_bytes"] += desc_bytes
        tf["shard_query_bytes"] += q_pad * (d_dim * 4 + 4)
        tf["bytes_to_device"] += desc_bytes + q_pad * (d_dim * 4 + 4)
        pending.dv, pending.gv = dv, gv
    return pending


def sharded_plan_fetch(runtime, pending: ShardedPending
                       ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Sync on a dispatched sharded wave and run the host merge:
    sentinel filter + delta-overflow fold per request.  This is the only
    device→host block of the sharded wave — a pipelined caller fetches
    wave N while wave N+1's shard_map launch is already in flight."""
    if pending.fetched:
        return pending.out
    plan, k, metric = pending.plan, pending.k, pending.metric
    queries_np, out = pending.queries_np, pending.out
    vals = gids = None
    if pending.dv is not None:
        vals = np.asarray(pending.dv)
        gids = np.asarray(pending.gv, dtype=np.int64)
    row = 0
    for e, spec in zip(plan.entries, pending.specs):
        ed, extra_ids = _extras_block(runtime, queries_np, e, spec.extra,
                                      metric)
        for j, r in enumerate(e.requests):
            if vals is not None:
                vrow, irow = vals[row], gids[row]
                valid = np.isfinite(vrow) & (irow >= 0)
                dr, ir = vrow[valid], irow[valid]
            else:
                dr = np.empty(0, np.float32)
                ir = np.empty(0, np.int64)
            row += 1
            if ed is not None:
                dr, ir = _merge_extras_row(dr, ir, ed[j], extra_ids, k)
            out[r] = (dr.astype(np.float32, copy=False),
                      ir.astype(np.int64, copy=False))
    pending.fetched = True
    return out


def _sharded_plan_topk_dense(mesh: Mesh, sh: ShardedDeviceIndex, runtime,
                             queries_np: np.ndarray, plan, k: int, *,
                             metric: str, axis: str):
    """Legacy per-entry dense-mask path — the parity oracle for the
    descriptor executor (``runtime.shard_descriptors = False``): one
    host-composed (N,) validity mask upload and one launch per entry.
    ``shard_mask_bytes`` counts what the descriptor path saves."""
    n = sh.n
    tf = runtime.traffic
    tf["shard_batches"] += 1
    queries = jnp.asarray(queries_np, f32)
    out = [(np.empty(0, np.float32), np.empty(0, np.int64))
           ] * plan.n_requests
    deleted = runtime.deleted
    for entry in plan.entries:
        full_mask = runtime.entry_mask(entry)
        extra_ids = (np.nonzero(full_mask[n:])[0].astype(np.int64) + n
                     if len(full_mask) > n else np.empty(0, np.int64))
        mask = full_mask[:n]
        if len(mask) < n:
            mask = np.pad(mask, (0, n - len(mask)))
        if deleted:
            mask[[i for i in deleted if i < n]] = False
        tf["shard_mask_bytes"] += int(mask.nbytes)
        tf["bytes_to_device"] += int(mask.nbytes)
        # pass the padded resident table; pad rows are masked False
        mask_pad = np.pad(mask, (0, sh.n_pad - n))
        with mesh:
            d, i = sharded_topk(mesh, queries[entry.requests, :],
                                sh.vectors, k, metric=metric, axis=axis,
                                valid_mask=jnp.asarray(mask_pad))
        d = np.asarray(d)
        i = np.asarray(i, dtype=np.int64)
        ed, extra_ids = _extras_block(runtime, queries_np, entry,
                                      extra_ids, metric)
        for row, r in enumerate(entry.requests):
            valid = np.isfinite(d[row]) & (i[row] >= 0)
            dr, ir = d[row][valid], i[row][valid]
            if ed is not None:
                dr, ir = _merge_extras_row(dr, ir, ed[row], extra_ids, k)
            out[r] = (dr, ir)
    return out


def replicate(mesh: Mesh, x: jax.Array) -> jax.Array:
    return jax.device_put(x, NamedSharding(mesh, P()))


def shard_rows(mesh: Mesh, x: jax.Array, axis: str = "data") -> jax.Array:
    return jax.device_put(
        x, NamedSharding(mesh, P(axis, *((None,) * (x.ndim - 1)))))
