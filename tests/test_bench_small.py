"""End-to-end smoke of bench.py's workload makers in --small mode — the
guard for the driver's headline artifact (bench.py runs unattended at
round end)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestBenchSmallMode:
    """Every bench workload maker must run end-to-end in --small mode on a
    CPU host."""

    @pytest.mark.slow
    def test_small_mode_subset_produces_json(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "--small",
             "--only",
             "moments,lasso,attention,attention_bwd,matmul_1b,lm_step"],
            capture_output=True, text=True, timeout=900, cwd=REPO, env=env,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        line = json.loads(r.stdout.strip().splitlines()[-1])
        assert line["unit"] == "GFLOP/s"
        # a CPU run never claims the chip
        assert line["on_chip"] is False and line["platform"] == "cpu"
        assert line["vs_baseline"] is None
        detail = json.loads(
            [l for l in r.stderr.splitlines() if l.startswith("{") and "gflops" in l][-1]
        )
        for row in ("moments_gflops", "lasso_gflops", "attention_gflops",
                    "attention_bwd_gflops", "matmul_1b_gflops", "lm_step_gflops"):
            assert detail[row] > 0, (row, detail)
        assert "errors" not in detail, detail.get("errors")
        assert not any(k.endswith("_mfu") for k in detail), detail
