"""The served path as the benchmark drives it, open loop.

``TracedEngine`` and ``RecordingBatcher`` subclass the program's
``RetrievalEngine`` and ``ContinuousBatcher`` and change nothing they
do: the engine wraps its three pipeline stages in profiler spans named
``<thread>/<stage>`` (the host side of the trace), and the batcher
records which requests each wave took and when each answer came back.
``OpenLoop`` sends a schedule of requests on its own thread, whatever
the system's state, while a serving thread drains the batcher.
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.serve.batching import ContinuousBatcher
from repro.serve.engine import Request, RetrievalEngine


class TracedEngine(RetrievalEngine):
    def _span(self, stage: str):
        import jax
        return jax.profiler.TraceAnnotation(
            f"{threading.current_thread().name}/{stage}")

    def plan_batch(self, *args, **kwargs):
        with self._span("plan_batch"):
            return super().plan_batch(*args, **kwargs)

    def dispatch_batch(self, *args, **kwargs):
        with self._span("dispatch_batch"):
            return super().dispatch_batch(*args, **kwargs)

    def fetch_batch(self, *args, **kwargs):
        with self._span("fetch_batch"):
            return super().fetch_batch(*args, **kwargs)


class RecordingBatcher(ContinuousBatcher):
    """Records, per ticket, when its answer came back and what it was,
    and per wave, the tickets it admitted."""

    def __init__(self, engine, **kwargs) -> None:
        super().__init__(engine, **kwargs)
        self.done: dict = {}            # ticket -> perf_counter at answer
        self.answers: dict = {}         # ticket -> (distances, ids)
        self.waves: List[Tuple[float, List[int]]] = []

    def next_wave(self):
        wave = super().next_wave()
        if wave:
            self.waves.append((time.perf_counter(), [q.seq for q in wave]))
        return wave

    def _record(self, q, resp) -> None:
        self.done[q.seq] = time.perf_counter()
        self.answers[q.seq] = (resp.distances, resp.ids)
        super()._record(q, resp)


class Server:
    """A thread that drains the batcher whenever requests are queued."""

    def __init__(self, batcher: RecordingBatcher) -> None:
        self.batcher = batcher
        self.work = threading.Event()
        self.stop = threading.Event()
        self.error: Optional[str] = None
        self.thread = threading.Thread(target=self._loop,
                                       name="bench-server", daemon=True)
        self.thread.start()

    def _loop(self) -> None:
        while not self.stop.is_set():
            self.work.wait(0.005)
            self.work.clear()
            if not self.batcher.pending():
                continue
            try:
                self.batcher.drain()
            except Exception:           # recorded; the requests stay unanswered
                self.error = traceback.format_exc()
                return

    def close(self) -> None:
        self.stop.set()
        self.work.set()
        self.thread.join(timeout=120)
        if self.thread.is_alive():
            raise RuntimeError("serving thread did not stop")


class OpenLoop:
    """Sends ``(offset_s, vector, pattern)`` requests at ``t0 + offset``.
    ``tickets[i]`` is request i's batcher ticket, ``lag[i]`` how late it
    was sent, on the sender's own clock."""

    def __init__(self, server: Server, schedule: Sequence, k: int) -> None:
        self.server = server
        self.schedule = schedule
        self.k = k
        self.tickets = np.full(len(schedule), -1, np.int64)
        self.lag = np.zeros(len(schedule))
        self.t0 = 0.0
        self.error: Optional[str] = None
        self.thread: Optional[threading.Thread] = None

    def start(self, t0: float) -> None:
        self.t0 = t0
        self.thread = threading.Thread(target=self._send, name="bench-client",
                                       daemon=True)
        self.thread.start()

    def _send(self) -> None:
        batcher = self.server.batcher
        try:
            for i, (off, vec, pattern) in enumerate(self.schedule):
                due = self.t0 + off
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                self.lag[i] = time.perf_counter() - due
                self.tickets[i] = batcher.submit(
                    Request(vector=vec, pattern=pattern, k=self.k))
                self.server.work.set()
        except Exception:
            self.error = traceback.format_exc()

    def join(self, timeout: float) -> None:
        self.thread.join(timeout)
        if self.thread.is_alive():
            raise RuntimeError("request sender did not finish")

    def wait_answered(self, deadline: float) -> None:
        """Until every sent request has its answer, the serving thread
        has failed, or ``deadline`` (perf_counter) has passed."""
        done = self.server.batcher.done
        while time.perf_counter() < deadline and self.server.error is None:
            if all(int(t) in done for t in self.tickets if t >= 0):
                return
            time.sleep(0.01)
