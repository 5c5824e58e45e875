"""``descriptors_per_request``, ``residual_verify_ms_per_wave`` and
``build_us_per_symbol``: each reader against counters given by hand,
and silent where the program has no such counter (the parent of the
change that added them)."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402


def _metric(name):
    return bench_run.load_module("metrics", name)


def _run(before: dict, after: dict, answered: int = 0):
    return bench_run.RunRecord(counters0=before, counters1=after,
                               answered=answered)


BUILD = {"time_build_esam_ms": 600.0, "time_build_states_ms": 150.0,
         "time_build_runtime_ms": 50.0, "esam_symbols": 200_000}


@pytest.mark.parametrize("name,before,after,answered,want", [
    ("descriptors_per_request", {"traffic_scan_descriptors": 10},
     {"traffic_scan_descriptors": 130}, 40, 3.0),
    ("residual_verify_ms_per_wave",
     {"pipeline_waves": 5, "time_residual_verify_ms": 2.0},
     {"pipeline_waves": 25, "time_residual_verify_ms": 62.0}, 0, 3.0),
    # totals before the window: 800 ms over 200,000 symbols
    ("build_us_per_symbol", BUILD, {}, 0, 4.0),
])
def test_reader_from_the_counters(name, before, after, answered, want):
    assert _metric(name).read(_run(before, after, answered)) == \
        pytest.approx(want)


@pytest.mark.parametrize("name,before,after,answered", [
    ("descriptors_per_request", {}, {"pipeline_waves": 3}, 5),
    ("descriptors_per_request", {}, {"traffic_scan_descriptors": 4}, 0),
    ("residual_verify_ms_per_wave", {}, {"pipeline_waves": 3}, 5),
    ("residual_verify_ms_per_wave", {},
     {"pipeline_waves": 0, "time_residual_verify_ms": 1.0}, 5),
    ("build_us_per_symbol", {"pipeline_waves": 3}, {}, 5),
    ("build_us_per_symbol", {**BUILD, "esam_symbols": 0}, {}, 5),
])
def test_reader_silent_without_counter(name, before, after, answered):
    assert _metric(name).read(_run(before, after, answered)) is None
