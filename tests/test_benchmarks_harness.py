"""Smoke of the scaling-benchmark harness (benchmarks/ — the reference's
per-algorithm config + runner + jobscript-generator tree,
benchmarks/kmeans/config.json:1-73, generate_jobscripts.py:12-50).
Runners execute in subprocesses at tiny sizes on a forced 2-device mesh;
the generator's sweep enumeration is checked in-process."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd):
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=600, cwd=REPO
    )


class TestGenerator:
    def test_enumerates_every_config(self, tmp_path):
        out = tmp_path / "runs.sh"
        r = _run([sys.executable, "benchmarks/generate_runs.py",
                  "--out", str(out)])
        assert r.returncode == 0, r.stderr[-500:]
        text = out.read_text()
        for algo in ("kmeans", "distance_matrix", "statistical_moments",
                     "lasso"):
            assert f"benchmarks/{algo}/heat_tpu.py" in text
        # strong AND weak points for every mesh entry
        assert text.count("strong") and text.count("weak")

    def test_rejects_unknown_algo(self):
        r = _run([sys.executable, "benchmarks/generate_runs.py",
                  "--algos", "nope"])
        assert r.returncode != 0


@pytest.mark.parametrize(
    "runner,extra",
    [
        ("kmeans", ["--clusters", "3", "--iterations", "3"]),
        ("distance_matrix", []),
        ("distance_matrix", ["--ring"]),
        ("statistical_moments", []),
        ("lasso", ["--sweeps", "3"]),
    ],
)
def test_runner_smoke(runner, extra):
    r = _run([
        sys.executable, f"benchmarks/{runner}/heat_tpu.py",
        "--n", "4000", "--features", "8", "--trials", "2", "--mesh", "2",
        *extra,
    ])
    assert r.returncode == 0, r.stderr[-800:]
    lines = [json.loads(l) for l in r.stdout.strip().splitlines()]
    assert any("compile_seconds" in l for l in lines)
    summary = lines[-1]
    assert summary["trials"] == 2 and summary["best_seconds"] > 0
    assert summary["devices"]["count"] == 2


@pytest.mark.slow
def test_serving_runner_smoke():
    """The serving loadgen runner (ISSUE 8): zero registry misses during
    the load window, no failures, the bench-honesty pair on the summary.
    Slow-marked (fresh-process jax import + fit + load, ~12s); the CI
    serving gate exercises the same runner end to end every sweep."""
    r = _run([
        sys.executable, "benchmarks/serving/heat_tpu.py",
        "--n", "512", "--features", "8", "--mesh", "2",
        "--requests", "40", "--rate", "400", "--max-batch", "4",
        "--endpoints", "kmeans,dense", "--digest",
    ])
    assert r.returncode == 0, r.stderr[-800:]
    lines = [json.loads(l) for l in r.stdout.strip().splitlines()]
    cmp_ = next(l["serving_compare"] for l in lines
                if "serving_compare" in l)
    assert cmp_["misses_during_load"] == 0
    assert cmp_["failed"] == 0 and cmp_["shed"] == 0
    assert cmp_["post_ok"] is True
    assert len(cmp_["digest"]) == 64
    summary = next(l for l in lines if l.get("bench") == "serving")
    assert summary["on_chip"] is False
    assert isinstance(summary["cpu_fallback"], str)
    assert summary["achieved_qps"] > 0
