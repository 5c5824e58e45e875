"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are those named ``/device:TPU:<n>``.  On each, the ``XLA
Ops`` line holds one event per operation that ran, named by its HLO
text (``%fusion.2 = f32[...] fusion(...)``), and the ``XLA Modules``
line one per program (``jit_<name>(<id>)``); ``read`` keeps the short
names (``fusion.2``, ``jit_<name>``).  Busy time is the union of
the operation intervals; the idle gaps between them are labelled by the
host span that covers most of each gap (the benchmark's spans are named
``<thread>/<stage>``, ``serving.TracedEngine``).  Times are in ns from
the start of the trace, the window from its start to its stop
(``Task Environment`` plane).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def module_name(event_name: str) -> str:
    """``jit_distance_topk_descriptors(12)`` -> ``jit_distance_topk_descriptors``."""
    return _MODULE_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.2 = f32[8]{0} fusion(...)`` -> ``fusion.2``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: np.ndarray) -> List[Tuple[int, int]]:
    """Merged ``[start, end)`` of an (n, 2) array of intervals."""
    if not len(intervals):
        return []
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [[int(iv[0, 0]), int(iv[0, 1])]]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], int(e))
        else:
            out.append([int(s), int(e)])
    return [(s, e) for s, e in out]


@dataclass
class Trace:
    window_ns: Tuple[int, int]
    # per device: (names, (n, 2) start/end ns) of operations and programs
    ops: List[Tuple[List[str], np.ndarray]] = field(default_factory=list)
    modules: List[Tuple[List[str], np.ndarray]] = field(default_factory=list)
    spans: Tuple[List[str], np.ndarray] = field(
        default_factory=lambda: ([], np.zeros((0, 2), np.int64)))

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def _clip(self, iv: np.ndarray) -> np.ndarray:
        lo, hi = self.window_ns
        return np.clip(iv, lo, hi)

    def busy_intervals(self, device: int) -> List[Tuple[int, int]]:
        return union(self._clip(self.ops[device][1]))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        return float(np.mean([sum(e - s for s, e in self.busy_intervals(d))
                              for d in range(len(self.ops))])) / 1e9

    def module_s(self, names) -> float:
        """Seconds the programs named ``names`` ran, averaged over
        devices."""
        if not self.modules:
            return 0.0
        total = 0.0
        for mnames, iv in self.modules:
            iv = self._clip(iv)
            total += sum(int(e - s) for m, (s, e) in zip(mnames, iv)
                         if m in names)
        return total / len(self.modules) / 1e9

    def module_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for mnames, _ in self.modules[:1]:
            for m in mnames:
                out[m] = out.get(m, 0) + 1
        return out

    def top_ops(self, n: int) -> list:
        """The ``n`` operations that took most device time, named
        ``<program>:<operation>``."""
        total: Dict[str, int] = {}
        for (names, iv), (mnames, miv) in zip(self.ops, self.modules):
            iv = self._clip(iv)
            order = np.argsort(miv[:, 0], kind="stable")
            starts = miv[order, 0]
            for name, (s, e) in zip(names, iv):
                j = int(np.searchsorted(starts, s, side="right")) - 1
                prog = (mnames[order[j]]
                        if j >= 0 and s < miv[order[j], 1] else "?")
                key = f"{prog}:{name}"
                total[key] = total.get(key, 0) + int(e - s)
        top = sorted(total.items(), key=lambda x: -x[1])[:n]
        return [[name, ns / 1e9 / max(len(self.ops), 1)] for name, ns in top]

    def gaps(self, device: int = 0) -> List[Tuple[int, int]]:
        busy = self.busy_intervals(device) if self.ops else []
        lo, hi = self.window_ns
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]

    def idle_gaps(self, n: int) -> list:
        """Idle device time by what the host was doing: each stretch of a
        gap goes to the host span that covers it, the latest-started
        where several do; what no span covers is ``no pipeline stage``
        (waiting for work, or in the batcher or the client).  The ``n``
        largest totals, in seconds."""
        names, iv = self.spans
        total: Dict[str, int] = {}
        for s, e in self.gaps():
            inside = np.flatnonzero((iv[:, 0] < e) & (iv[:, 1] > s)) \
                if len(iv) else []
            cuts = sorted({s, e, *(int(x) for j in inside for x in iv[j]
                                   if s < x < e)})
            for a, b in zip(cuts[:-1], cuts[1:]):
                cover = [j for j in inside if iv[j, 0] <= a and iv[j, 1] >= b]
                label = (names[max(cover, key=lambda j: iv[j, 0])]
                         if cover else "no pipeline stage")
                total[label] = total.get(label, 0) + (b - a)
        top = sorted(total.items(), key=lambda x: -x[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]


def _events(line, rename) -> list:
    return [[rename(ev.name), int(ev.start_ns),
             int(ev.start_ns + ev.duration_ns)] for ev in line.events]


def read(path: str, stages=("plan_batch", "dispatch_batch", "fetch_batch")
         ) -> dict:
    """What the reduction uses out of an ``.xplane.pb``, as plain lists
    (JSON-ready): per device plane its operations (short names) and
    programs, and the benchmark's host spans (``<thread>/<stage>`` for
    ``stage`` in ``stages``), each ``[name, start_ns, end_ns]``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = [], []
    start = stop = None
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            start = stats.get("profile_start_time")
            stop = stats.get("profile_stop_time")
        if _DEVICE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices.append({
                "ops": (_events(lines["XLA Ops"], op_name)
                        if "XLA Ops" in lines else []),
                "modules": (_events(lines["XLA Modules"], module_name)
                            if "XLA Modules" in lines else [])})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line, str)
                          if ev[0].rsplit("/", 1)[-1] in stages]
    if start is not None and stop is not None:
        window = [0, int(stop) - int(start)]
    else:
        every = [ev for d in devices for ev in d["ops"]] + spans
        window = ([min(ev[1] for ev in every), max(ev[2] for ev in every)]
                  if every else [0, 0])
    return {"window_ns": window, "devices": devices, "spans": spans}


def _arrays(events) -> Tuple[List[str], np.ndarray]:
    return ([ev[0] for ev in events],
            np.asarray([ev[1:] for ev in events], np.int64).reshape(-1, 2))


def from_raw(raw: dict) -> Trace:
    return Trace(window_ns=tuple(raw["window_ns"]),
                 ops=[_arrays(d["ops"]) for d in raw["devices"]],
                 modules=[_arrays(d["modules"]) for d in raw["devices"]],
                 spans=_arrays(raw["spans"]))


def load(path: str) -> Trace:
    return from_raw(read(path))
