"""The program's own spans and counters as the benchmark reads them.

``data/program_spans.trace.json.gz`` is ``tracefile.read``'s extraction
of half a second of a pipelined batcher on the CPU, kept full by a
sender thread, with every stage span the program writes (no
``serving.TracedEngine``).  The CPU has no device plane, so the whole
window is one idle gap and the breakdown shows what labels it.  Then
the names the scan metrics key on, pinned to the program's jits, and
the five counter metrics in a traced rehearsal of each cell.
"""

from __future__ import annotations

import gzip
import inspect
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as bench_run, tracefile  # noqa: E402
from bench.tests.test_bench_rehearsal import CELLS, bench  # noqa: E402,F401

TRACE = Path(__file__).resolve().parent / "data" / "program_spans.trace.json.gz"
SCAN = bench_run.load_module("kernels", "scan")
COUNTER_METRICS = ("queue_wait_ms_per_request", "submit_lock_ms_per_request",
                   "fetch_sync_ms_per_wave", "scan_work_ratio",
                   "compile_ms_in_window")


def _raw():
    with gzip.open(TRACE, "rt") as f:
        return json.load(f)


def test_idle_stretches_go_to_the_innermost_program_span():
    tr = tracefile.from_raw(_raw())
    idle = dict(map(tuple, tr.idle_gaps(100)))
    assert abs(sum(idle.values()) - tr.window_s) < 1e-6
    # a child's stretch goes to the child, not to the stage around it
    for label in ("repro-executor/launch_scan", "repro-executor/sync",
                  "repro-planner/stage_queries", "bench-server/collect",
                  "bench-client/engine_lock"):
        assert idle.get(label, 0) > 0, label
    stage_s = sum(e - s for n, s, e in _raw()["spans"]
                  if n == "repro-executor/dispatch_batch") / 1e9
    assert idle.get("repro-executor/dispatch_batch", 0) < stage_s
    assert idle.get("no pipeline stage", 0) < 0.05 * tr.window_s


def test_the_three_stage_labels_come_from_the_program_alone():
    """What ``read`` keeps by default is already written by the program,
    so the breakdown's labels stay when the benchmark's wrappers go."""
    stages = inspect.signature(tracefile.read).parameters["stages"].default
    raw = _raw()
    raw["spans"] = [ev for ev in raw["spans"]
                    if ev[0].rsplit("/", 1)[-1] in stages]
    labels = {n for n, _ in tracefile.from_raw(raw).idle_gaps(10)}
    assert {"repro-planner/plan_batch", "repro-executor/dispatch_batch",
            "repro-executor/fetch_batch"} <= labels


def test_scan_modules_are_the_program_jits():
    import jax
    import jax.numpy as jnp
    from repro.kernels.distance_topk import distance_topk_descriptors
    from repro.kernels.quant import _sq8_topk_descriptors
    assert SCAN.MODULES == tuple(
        f"jit_{f.__name__}"
        for f in (distance_topk_descriptors, _sq8_topk_descriptors))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    lowered = distance_topk_descriptors.lower(
        f32(256, 8), i32(256), jax.ShapeDtypeStruct((256,), jnp.bool_),
        f32(128, 8), i32(128, 1), i32(8), i32(8), i32(8), i32(0), i32(0),
        i32(0), i32(0), f32(0, 8), 16, n_desc=128, impl="xla")
    assert lowered.as_text().startswith(f"module @{SCAN.MODULES[0]} ")


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_the_counter_metrics(bench, capsys, cell):
    rc, res = bench("--workload", cell, "--seed", "2147483659",
                    "--trace", "1", capsys=capsys, seconds="0.3")
    assert rc == 0 and res["correct"] is True
    assert set(COUNTER_METRICS) <= set(res["metrics"])
    ratio = res["metrics"]["scan_work_ratio"]["value"]
    assert ratio >= 1.0
