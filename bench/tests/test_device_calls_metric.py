"""``device_calls_per_wave``: the reader against counters given by hand
(silent where the program has no such counter), and in a traced
rehearsal of each cell.  Where every predicate of the cell's mix plans
to scans, every wave is a scan-only wave: one upload, the scan and
merge launches and one download, and where the cell scans in SQ8, the
certificate's read back and, for a batch it does not certify, the fp32
relaunch.  A cell whose mix reaches a graph beam launches more, and is
held to one upload, one launch and one download at least."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402
from bench.tests.test_bench_rehearsal import CELLS, bench  # noqa: E402,F401

METRIC = bench_run.load_module("metrics", "device_calls_per_wave")


def _run(before: dict, after: dict):
    return bench_run.RunRecord(counters0=before, counters1=after)


def test_calls_per_wave_from_the_counters():
    run = _run({"pipeline_waves": 10, "traffic_host_device_calls": 40},
               {"pipeline_waves": 30, "traffic_host_device_calls": 120})
    assert METRIC.read(run) == 4.0


@pytest.mark.parametrize("after", [
    {"pipeline_waves": 12},                                  # no counter
    {"pipeline_waves": 0, "traffic_host_device_calls": 8},   # no wave
])
def test_silent_without_counter_or_waves(after):
    assert METRIC.read(_run({}, after)) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_the_calls(bench, capsys, monkeypatch,
                                            cell):
    sq8 = bench_run.load_cell(cell)[2]["quantize"] == "sq8"
    set_up, plans = bench_run.set_up, {}

    def recording(*args, **kwargs):
        st = set_up(*args, **kwargs)
        plans["scan_only"] = all(st.scanned.values())
        return st

    monkeypatch.setattr(bench_run, "set_up", recording)
    rc, res = bench("--workload", cell, "--seed", "2147483659",
                    "--trace", "1", capsys=capsys, seconds="0.3")
    assert rc == 0 and res["correct"] is True
    calls = res["metrics"]["device_calls_per_wave"]["value"]
    if not plans["scan_only"]:
        assert calls >= 3
    elif sq8:
        assert 4 <= calls <= 6
    else:
        assert calls == 4
