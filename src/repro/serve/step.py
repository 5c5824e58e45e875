"""The double-buffered host staging ring for the pipelined retrieval
executor.

``StagingRing`` (DESIGN.md §7): the planner thread assembles wave N+1's
query matrix into one of two preallocated host buffers while wave N's
launches execute, so wave formation never allocates on the hot path and
the upload for wave N+1 reads from a buffer the in-flight wave cannot
touch.  A slot is held from planning until the wave's results are
fetched; with a depth-1 plan queue plus one in-flight wave, two slots
are exactly enough and ``acquire`` throttles the planner when it runs
more than a full pipeline ahead.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np


class StagingStall(TimeoutError):
    """A staging-slot lease timed out: every upload slot stayed leased
    past the deadline, i.e. the fetch stage is not draining and the
    pipeline is wedged.  Carries the ring depth and the observed wait so
    the stall is diagnosable (and countable in ``maintenance_stats`` via
    ``staging_stalls``) instead of surfacing as an anonymous
    ``TimeoutError``."""

    def __init__(self, depth: int, wait_ms: float):
        super().__init__(
            f"StagingRing.acquire: all {depth} upload slots leased after "
            f"{wait_ms:.0f} ms — the fetch stage is not draining "
            f"(pipeline stalled)")
        self.depth = depth
        self.wait_ms = wait_ms


class StagingSlot:
    """One leased buffer of a ``StagingRing``: ``view(n)`` is the filled
    (n, d) prefix the dispatch stage uploads from; ``release()`` returns
    the slot to the ring (idempotent)."""

    def __init__(self, ring: "StagingRing", idx: int, n: int) -> None:
        self._ring = ring
        self._idx = idx
        self._n = n

    def view(self, n: Optional[int] = None) -> np.ndarray:
        return self._ring._bufs[self._idx][:self._n if n is None else n]

    def release(self) -> None:
        if self._idx >= 0:
            self._ring._release(self._idx)
            self._idx = -1


class StagingRing:
    """Double-buffered host staging for wave query matrices.

    ``acquire(queries)`` copies the wave's (n, d) query rows into a free
    preallocated slot (growing the slot's row capacity geometrically if
    the wave is larger than anything seen), blocking while both slots
    are leased — i.e. while a full pipeline (one planned + one in-flight
    wave) is outstanding.  This bounds planner run-ahead without a
    second queue and makes wave formation allocation-free at steady
    state."""

    def __init__(self, dim: int, capacity: int = 64,
                 slots: int = 2) -> None:
        self.dim = int(dim)
        self._bufs = [np.empty((capacity, dim), np.float32)
                      for _ in range(slots)]
        self._free = list(range(slots))
        self._cv = threading.Condition()
        self.grows = 0          # observability: hot-path reallocations
        self.waits = 0          # acquire() calls that had to block
        self.stalls = 0         # leases that timed out (StagingStall)

    def acquire(self, queries: np.ndarray,
                timeout: Optional[float] = None) -> StagingSlot:
        q = np.asarray(queries, dtype=np.float32)
        n = q.shape[0]
        t0 = time.perf_counter()
        with self._cv:
            if not self._free:
                self.waits += 1
            if not self._cv.wait_for(lambda: bool(self._free),
                                     timeout=timeout):
                self.stalls += 1
                raise StagingStall(
                    depth=len(self._bufs),
                    wait_ms=(time.perf_counter() - t0) * 1e3)
            idx = self._free.pop()
        buf = self._bufs[idx]
        if buf.shape[0] < n:
            cap = max(n, buf.shape[0] * 2)
            self._bufs[idx] = buf = np.empty((cap, self.dim), np.float32)
            self.grows += 1
        buf[:n] = q
        return StagingSlot(self, idx, n)

    def _release(self, idx: int) -> None:
        with self._cv:
            self._free.append(idx)
            self._cv.notify()

