"""The examples run clean: each in its own process, exit code 0."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.parametrize("script", ["quickstart.py", "pattern_search.py"])
def test_example_runs(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable,
                          os.path.join(ROOT, "examples", script)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
