"""A configuration whose rows carry sequences of their own takes only new
files: a corpus module, a configuration naming it, a traffic mix, and
entries appended to ``BENCHMARK.json`` (the cell, and the cell's name
in the lists of the metrics it reports).  Such a cell is rehearsed in a
copy of the benchmark and checks correct; the same run with one
answer's row swapped for a row outside its predicate does not; the
benchmark's own tests that read every configuration or run every cell
pass in the copy on the new cell.  The per-row members equal a brute
evaluation of every row.
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
sys.path.insert(0, str(ROOT))

from bench import predicates  # noqa: E402

SEED = "2147483659"

CORPUS = '''"""Rows of their own: each row a random string over a, c, g, t of 8
to 40 symbols, with the label rule's clustered Gaussian vectors."""

import numpy as np

from bench import corpus


def rows(n, seed, cfg):
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xAC67]))
    lens = rng.integers(8, 41, n)
    text = np.frombuffer(b"acgt", np.uint8)[
        rng.integers(0, 4, int(lens.sum()))].tobytes().decode()
    ends = np.cumsum(lens).tolist()
    seqs = [text[e - m:e] for e, m in zip(ends, lens.tolist())]
    return corpus.vectors(n, cfg["dim"], seed, cfg["normalize"]), seqs


def queries(count, seed, cfg, stream):
    return corpus.queries(count, cfg["dim"], seed, cfg["normalize"], stream)
'''

CONFIG = {
    "name": "acgt", "corpus": "acgt", "rows": 2048, "dim": 32,
    "metric": "l2", "k": 10, "normalize": False, "T": 1000000000,
    "quantize": "sq8", "accum": "f32", "plan_mode": "adaptive",
    "reduced": {},
    "check": {"unanswered": 0, "wrong_answers": 0, "dist_err": 1e-06}}

MIX = {"arrivals": "poisson", "rate_per_s": 100, "k": 10, "tenants": 1,
       "predicates": ["ac", "gta", "CONTAINS 'tt'", "ca AND tg", "NOT cg",
                      "LIKE 'a%t'"]}

# runs the cell, then again with the first answer of the window given a
# row outside its predicate (by the program's own parser) in place of
# its last id; prints both result lines
DRIVE = '''import sys

from bench import corpus, run
from repro.core.predicate import parse_predicate
from repro.serve.engine import RetrievalEngine

ARGS = ["--rehearse", "--workload", "acgt.mix", "--seed", sys.argv[1],
        "--seconds", "0.2", "--trace", "0"]
if run.main(ARGS):
    sys.exit(1)
_, _, cfg, _ = run.load_cell("acgt.mix")
_, seqs = corpus.rows(cfg, run.REHEARSE_ROWS, int(sys.argv[1]))
fetch, window = RetrievalEngine.fetch_batch, run.serve_window
state = {"window": False, "swapped": None}


def broken(self, pending):
    out = fetch(self, pending)
    if not state["window"] or state["swapped"] is not None:
        return out
    for r, (d, ids) in enumerate(out):
        if len(ids):
            pred = parse_predicate(pending.wave.patterns[r])
            row = next(j for j, s in enumerate(seqs) if not pred.matches(s))
            ids = ids.copy()
            ids[-1] = row
            out[r] = (d, ids)
            state["swapped"] = (pending.wave.patterns[r], row)
            break
    return out


def planted(*args, **kwargs):
    state["window"] = True
    return window(*args, **kwargs)


RetrievalEngine.fetch_batch = broken
run.serve_window = planted
sys.exit(run.main(ARGS) or state["swapped"] is None)
'''


def _env(tmp):
    return dict(os.environ, PYTHONPATH=str(ROOT / "src"),
                JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"))


@pytest.fixture(scope="module")
def bench_copy(tmp_path_factory):
    """A copy of the benchmark with the new files and entries added."""
    tmp = tmp_path_factory.mktemp("per_row")
    shutil.copy(ROOT / "BENCHMARK.json", tmp)
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "bench" / "corpora" / "acgt.py").write_text(CORPUS)
    (tmp / "bench" / "configs" / "acgt.json").write_text(json.dumps(CONFIG))
    (tmp / "bench" / "traffic" / "acgt.mix.json").write_text(json.dumps(MIX))
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "acgt", "source": "random strings over four letters",
        "file": "bench/configs/acgt.json", "reduced": [],
        "why": "rows with sequences of their own"})
    spec["workloads"].append({
        "name": "acgt.mix", "config": "acgt", "traffic": "acgt.mix",
        "chips": 1, "why": "multi-symbol CONTAINS, AND, NOT and LIKE"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("acgt.mix")
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp / "drive.py").write_text(DRIVE)
    return tmp


@pytest.fixture(scope="module")
def results(bench_copy):
    """The two result lines of ``DRIVE`` in the copy."""
    p = subprocess.run([sys.executable, "drive.py", SEED], cwd=bench_copy,
                       env=_env(bench_copy), capture_output=True,
                       text=True, timeout=600)
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    assert p.returncode == 0 and len(lines) == 2, p.stdout + p.stderr
    return lines


def test_rows_of_their_own_need_only_new_files(results):
    res = results[0]
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["check"]["wrong_answers"]["value"] == 0


def test_one_non_member_row_fails_the_check(results):
    res = results[1]
    assert res["correct"] is False
    assert res["check"]["wrong_answers"]["value"] == 1
    assert res["check"]["dist_err"]["value"] <= \
        res["check"]["dist_err"]["limit"]


def test_the_benchmark_tests_pass_on_the_new_cell(bench_copy):
    """The copy's yardstick tests, which read every configuration, and
    every test parametrised over the cells, on the new cell."""
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:xdist", "bench/tests", "-k", "yardstick or acgt.mix"],
        cwd=bench_copy, env=_env(bench_copy), capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    passed = {x.split("::")[1].split()[0] for x in p.stdout.splitlines()
              if "::" in x and " PASSED" in x}
    assert {"test_cell_result_line[acgt.mix-0]",
            "test_high_control_fails[acgt.mix]",
            "test_traced_rehearsal_reports_the_calls[acgt.mix]",
            "test_traced_rehearsal_reports_the_counter_metrics[acgt.mix]",
            "test_predicate_evaluator_agrees_with_the_program_parser",
            "test_benchmark_json_names_a_file_for_every_entry"} <= passed


def test_per_row_members_equal_a_brute_evaluation():
    rng = np.random.default_rng(7)
    seqs = ["".join(rng.choice(list("acgt"), rng.integers(1, 30)))
            for _ in range(1500)]
    seqs += seqs[:300]                              # rows that share one
    texts = ["ac", "CONTAINS 'gtt'", "ca AND tg", "a AND c AND NOT g",
             "ac OR gg", "(ac OR gg) AND NOT LIKE '%t'", "NOT cg",
             "LIKE 'a%t'", "LIKE '_c%'", "LIKE 'acgt'", "NOT (a OR c)",
             "tt AND (LIKE 'g%' OR NOT aa)"]
    got = predicates.members(texts, seqs)
    assert set(got) == set(texts)
    for text in texts:
        fn = predicates.compile_text(text)
        want = [r for r, s in enumerate(seqs) if fn(s)]
        assert got[text].dtype == np.int64
        assert got[text].tolist() == want, text
    assert 0 < len(got["ac"]) < len(seqs) and len(got["NOT (a OR c)"]) > 0
