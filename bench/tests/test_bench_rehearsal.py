"""The benchmark's whole run at a tiny size on the CPU (``--rehearse``:
Pallas kernels in interpret mode), through the same harness code as on
the chip: every cell's result line, the comparison failing a lower
precision and planted faults, and the refusal to run without a TPU.

Runs happen in this process (a chip belongs to one process, and
``--rehearse`` never touches one); JAX settings the harness changes are
put back after each test.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SECONDS = "0.05"


@pytest.fixture
def bench(monkeypatch, tmp_path):
    """``bench.run.main`` with the CPU, interpret-mode Pallas and a
    compilation cache of the test's own; returns a function of the
    arguments that gives (exit code, result or None)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    sys.path.insert(0, str(ROOT))
    from bench import run

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("REPRO_IMPL", "pallas")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}

    def call(*args, capsys=None, seconds=SECONDS):
        rc = run.main(["--rehearse", "--seconds", seconds, *args])
        out = capsys.readouterr().out.strip().splitlines() if capsys else []
        last = json.loads(out[-1]) if out and out[-1].startswith("{") \
            else None
        return rc, last

    yield call
    for k, v in keep.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


# every cell untraced; the two configurations traced, over a window long
# enough for waves to be dispatched inside it
RUNS = [(c, "0") for c in CELLS] + [(c, "1") for c in CELLS[:2]]


@pytest.mark.parametrize("cell,trace", RUNS)
def test_cell_result_line(bench, capsys, cell, trace):
    rc, res = bench("--workload", cell, "--seed", "2147483659",
                    "--trace", trace, capsys=capsys,
                    seconds="0.15" if trace == "1" else SECONDS)
    assert rc == 0
    assert KEYS <= set(res) and list(res)[-1] == "check"
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = [m["name"] for m in SPEC["per_layer" if trace == "1"
                                    else "end_to_end"]
            if cell in m.get("workloads", [cell])]
    # device-trace and memory readings are silent on the CPU; the
    # per-wave counters need a wave dispatched inside the window
    got = set(res["metrics"])
    assert got <= set(want)
    host = {"setup_s", "p50_ms", "p99_ms", "qps", "client_lag_ms"}
    assert host & set(want) <= got
    per_wave = {"requests_per_wave", "planner_wait_ms_per_wave",
                "plan_ms_per_wave", "dispatch_ms_per_wave",
                "bytes_to_device_per_wave"}
    assert not per_wave & got or per_wave & set(want) <= got
    if trace == "1":
        assert {"busy_s", "window_s", "count"} <= set(res["device"])
        assert "breakdown" in res


def test_bf16_control_fails(bench, capsys):
    """The program's own lower-precision path (bf16 operands in the
    scan); the glove cell's scans always run in fp32 (no SQ8)."""
    rc, res = bench("--workload", "glove100-tags.contains", "--seed", "11",
                    "--control", "bf16", capsys=capsys)
    assert rc == 0 and res["correct"] is False
    assert res["check"]["dist_err"]["value"] > \
        res["check"]["dist_err"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_high_control_fails(bench, capsys, cell):
    """The control: the reference one precision step below the
    configuration's float32 at ``highest`` (``bf16_3x``), in the
    program's place, at the committed limits."""
    rc, res = bench("--workload", cell, "--seed", "2147483701",
                    "--control", "high", capsys=capsys,
                    seconds="1.0")
    assert rc == 0 and res["correct"] is False
    assert res["check"]["dist_err"]["value"] > \
        res["check"]["dist_err"]["limit"]


def test_sweep_reports_a_knee(bench, capsys):
    """``--rates`` runs one window per rate plus the refined ones, and
    prints no result line."""
    rc, _ = bench("--workload", CELLS[0], "--seed", "17", "--rates",
                  "20,40", "--refine", "1", seconds="0.2")
    lines = capsys.readouterr().out.splitlines()
    rates = [json.loads(x[len("sweep "):])["rate"] for x in lines
             if x.startswith("sweep {")]
    assert rc == 0 and rates[:2] == [20, 40] and len(rates) == 3
    assert lines[-1].startswith("sweep: knee")
    assert not any(x.startswith("{") for x in lines)


def _reverse_ids(fn):
    def broken(*args, **kwargs):
        vals, gids = fn(*args, **kwargs)
        return vals, np.asarray(gids)[:, ::-1].copy()
    return broken


def _drop_half(fetch):
    seen = [0]

    def broken(self, pending):
        out = []
        for d, i in fetch(self, pending):
            seen[0] += 1
            out.append((d[:0], i[:0]) if seen[0] % 2 else (d, i))
        return out
    return broken


def _stale(fetch):
    last = {}

    def broken(self, pending):
        out = fetch(self, pending)
        prev = last.get("out", out)
        last["out"] = out
        return [prev[min(r, len(prev) - 1)] for r in range(len(out))]
    return broken


@pytest.mark.parametrize("fault", ["answer_altered", "half_dropped",
                                   "state_unchanged"])
def test_planted_fault_fails(bench, capsys, monkeypatch, fault):
    """A fault planted under the timed path makes ``correct`` false:
    the scan kernel's ids given in reverse order, every other
    answer left out, or each wave answered with the previous
    wave's results."""
    from repro.core.packed import PackedRuntime
    from repro.kernels import ops
    if fault == "answer_altered":
        monkeypatch.setattr(ops, "topk_segmented_desc",
                            _reverse_ids(ops.topk_segmented_desc))
    elif fault == "half_dropped":
        monkeypatch.setattr(PackedRuntime, "fetch",
                            _drop_half(PackedRuntime.fetch))
    else:
        monkeypatch.setattr(PackedRuntime, "fetch",
                            _stale(PackedRuntime.fetch))
    rc, res = bench("--workload", "glove100-tags.contains", "--seed", "13",
                    capsys=capsys)
    assert rc == 0 and res["correct"] is False
    assert res["check"]["wrong_answers"]["value"] > 0


def _run_cli(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_exits_without_result():
    p = _run_cli(ROOT, "--workload", CELLS[0], "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_bare_benchmark_directory_exits_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path, "--workload", CELLS[0], "--seed", "1",
                 "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
