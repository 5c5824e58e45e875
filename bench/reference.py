"""The plain reference: brute-force top-k over the live rows that satisfy
a predicate, in float64 on the host, and the comparison that decides
``correct``.  It imports nothing of the program.

Copied from ``chip_smoke.Reference`` / ``check_exact``: a float32
prefilter keeps the k + 32 nearest candidates, which are then ranked in
float64, ties broken by id.  An answer is right when it equals the
reference id for id, or differs only where two rows lie within the
distance limit of each other (a float32 near-tie).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

MARGIN = 32


class Reference:
    """``members``: per predicate text, the sorted ids of the rows that
    satisfy it (``predicates.members``)."""

    def __init__(self, vecs: np.ndarray, metric: str,
                 members: Dict[str, np.ndarray]) -> None:
        self.vecs = vecs
        self.metric = metric
        self._members = members
        self.y2 = np.einsum("nd,nd->n", vecs, vecs, dtype=np.float64)

    def members(self, pattern: str) -> np.ndarray:
        return self._members[pattern]

    def exact(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        y = self.vecs[ids].astype(np.float64)
        q = q.astype(np.float64)
        if self.metric == "l2":
            diff = y - q
            return np.einsum("nd,nd->n", diff, diff)
        return -(y @ q)

    def scale(self, q: np.ndarray, ids: np.ndarray) -> float:
        """The magnitude a float32 distance of ``q`` against ``ids`` is
        rounded at: |x|^2 + max |y|^2 for L2, |x| max |y| for inner
        product."""
        x2 = float(q.astype(np.float64) @ q)
        y2 = float(self.y2[ids].max()) if len(ids) else 0.0
        return x2 + y2 if self.metric == "l2" else float(np.sqrt(x2 * y2))

    def topk(self, queries: np.ndarray, patterns: Sequence[str], k: int
             ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Per request (ids, float64 distances), grouped by predicate so
        each predicate's rows are gathered once."""
        out: List = [None] * len(patterns)
        groups: Dict[str, List[int]] = {}
        for r, p in enumerate(patterns):
            groups.setdefault(p, []).append(r)
        for p, rows in groups.items():
            cand = self.members(p)
            y = self.vecs[cand]
            for lo in range(0, len(rows), 64):
                part = rows[lo:lo + 64]
                xy = y @ queries[part].T                # float32 prefilter
                for j, r in enumerate(part):
                    approx = (self.y2[cand] - 2.0 * xy[:, j]
                              if self.metric == "l2" else -xy[:, j])
                    keep = cand
                    if len(cand) > k + MARGIN:
                        keep = cand[np.argpartition(approx, k + MARGIN)
                                    [:k + MARGIN]]
                    dist = self.exact(queries[r], keep)
                    order = np.lexsort((keep, dist))[:k]
                    out[r] = (keep[order], dist[order])
        return out


def compare(ref: Reference, queries: np.ndarray, patterns: Sequence[str],
            answers: Sequence[Tuple[np.ndarray, np.ndarray]], k: int,
            dist_limit: float) -> dict:
    """Readings of the answers against the reference:

    ``wrong_answers``: answers that are not the reference's top-k, up to
    near-ties (rows whose exact distances lie within ``dist_limit`` of
    the reference's at the same rank, scaled as ``Reference.scale``);
    ``dist_err``: the widest gap between a returned distance and the
    reference's at the same rank, as a share of that scale.
    ``examples`` holds up to four wrong answers."""
    wrong, worst, examples = 0, 0.0, []
    for r, ((want, wd), (gd, got)) in enumerate(
            zip(ref.topk(queries, patterns, k), answers)):
        got = np.asarray(got, np.int64)
        gd = np.asarray(gd, np.float64)
        scale = max(ref.scale(queries[r], want), 1e-30)
        if len(got) != len(want):
            wrong += 1
            worst = max(worst, 1.0)
            examples.append((r, patterns[r], got.tolist(), want.tolist()))
            continue
        if not len(got):
            continue
        err = float(np.max(np.abs(gd - wd))) / scale
        worst = max(worst, err)
        if got.tolist() == want.tolist():
            continue
        members = np.isin(got, ref.members(patterns[r]))
        ok = (np.all((got >= 0) & (got < len(ref.vecs))) and members.all()
              and len(set(got.tolist())) == len(got)
              and np.all(np.abs(ref.exact(queries[r], got) - wd)
                         <= dist_limit * scale))
        if not ok:
            wrong += 1
            if len(examples) < 4:
                examples.append((r, patterns[r], got.tolist(),
                                 want.tolist()))
    return {"wrong_answers": wrong, "dist_err": worst, "examples": examples}


def bf16(a: np.ndarray) -> np.ndarray:
    """``a`` rounded to bfloat16 (to nearest, ties to even), as float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def control_high(ref: Reference, queries: np.ndarray,
                 patterns: Sequence[str], k: int):
    """The control: the reference put in the program's place and computed
    one precision step below the configuration's float32 at ``highest``,
    at ``high`` (three bf16 passes, ``bf16_3x``): each operand is split
    into a bf16 head and a bf16 remainder, and the head*head,
    head*remainder and remainder*head products (exact in float32) are
    summed in float32.  Done in NumPy on the host, so no compiler can
    fold the rounding away.  Returns answers in the program's
    (distances, ids) form."""
    out: List = [None] * len(patterns)
    groups: Dict[str, List[int]] = {}
    for r, p in enumerate(patterns):
        groups.setdefault(p, []).append(r)
    for p, rows in groups.items():
        cand = ref.members(p)
        y = ref.vecs[cand]
        x = queries[rows].astype(np.float32)
        yh, xh = bf16(y), bf16(x)
        yl, xl = bf16(y - yh), bf16(x - xh)
        xy = xh @ yh.T + xh @ yl.T + xl @ yh.T
        if ref.metric == "l2":
            d = (np.sum(x * x, 1, keepdims=True) + np.sum(y * y, 1)[None]
                 - 2.0 * xy)
        else:
            d = -xy
        for j, r in enumerate(rows):
            top = np.argpartition(d[j], k)[:k] if len(cand) > k else \
                np.arange(len(cand))
            top = top[np.lexsort((cand[top], d[j][top]))]
            out[r] = (d[j][top], cand[top])
    return out
