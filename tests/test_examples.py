"""Smoke tests for the examples/ scripts — each runs as a subprocess on the
test mesh the way a user would run it (the reference CI imports its examples
nowhere; running them is the only honest check)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(relpath, timeout=420):
    # a fixed 2-device CPU mesh: conftest has pinned XLA_FLAGS for the
    # parent, which would otherwise set the device count here
    env = dict(
        os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    return subprocess.run(
        [sys.executable, os.path.join(REPO, relpath)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


class TestExamples:
    def test_knn_demo(self):
        r = _run("examples/classification/demo_knn.py")
        assert r.returncode == 0, r.stderr[-1500:]
        assert "mean accuracy" in r.stdout
        # the reference demo's bar: fold accuracy well above chance (1/3)
        mean = float(r.stdout.strip().splitlines()[-1].split()[-1])
        assert mean > 0.9

    def test_lasso_demo(self):
        r = _run("examples/lasso/demo.py")
        assert r.returncode == 0, r.stderr[-1500:]
        assert "active coefficients per lambda:" in r.stdout
        # the lasso path must shrink: more actives at small lambda than large
        import ast

        actives = ast.literal_eval(
            r.stdout.split("active coefficients per lambda:")[1].splitlines()[0].strip()
        )
        assert actives[0] > actives[-1]

    def test_kclustering_demo(self):
        r = _run("examples/cluster/demo_kclustering.py")
        assert r.returncode == 0, r.stderr[-1500:]

    def test_ragged_layout_demo(self):
        # the redistribute_ ragged-map substitute as a demonstration
        # (PARITY.md "redistribute_ and ragged target maps")
        r = _run("examples/ragged_layout.py")
        assert r.returncode == 0, r.stderr[-1500:]
        assert "raises as documented" in r.stdout
        assert "ragged-layout result: OK" in r.stdout

    @pytest.mark.slow
    def test_lm_training(self):
        # flagship LM converging on the 3-gram task (asserts internally
        # that held-out perplexity at least halves from the uniform start)
        r = _run("examples/nn/lm_training.py", timeout=560)
        assert r.returncode == 0, r.stderr[-1500:]
        assert "converged: perplexity" in r.stdout

    @pytest.mark.slow
    def test_mnist_demo(self):
        r = _run("examples/nn/mnist.py", timeout=300)
        assert r.returncode == 0, r.stderr[-1500:]
        assert "eval accuracy" in r.stdout

    @pytest.mark.slow
    def test_daso_training_demo(self):
        r = _run("examples/nn/daso_training.py", timeout=300)
        assert r.returncode == 0, r.stderr[-1500:]

    @pytest.mark.slow
    def test_ring_attention_demo(self):
        r = _run("examples/long_context/ring_attention_demo.py", timeout=300)
        assert r.returncode == 0, r.stderr[-1500:]
        assert "max |diff|" in r.stdout

    @pytest.mark.slow
    def test_scaleout_tour(self):
        # pipeline/expert/FSDP schedules each check against their oracle
        # internally; the script asserts and exits non-zero on mismatch
        r = _run("examples/nn/scaleout_tour.py", timeout=420)
        assert r.returncode == 0, r.stderr[-1500:]
        assert "all three schedules match" in r.stdout

    @pytest.mark.slow
    def test_multihost_demo(self):
        # the one example that spawns ITS OWN 2-process jax.distributed run
        import socket

        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        keep = ("PATH", "HOME", "LANG", "LC_ALL", "TMPDIR", "TEMP", "TMP")
        env = {k: os.environ[k] for k in keep if k in os.environ}
        env["PYTHONPATH"] = REPO
        script = os.path.join(REPO, "examples/multihost/demo_multihost.py")
        if os.path.exists("/tmp/demo_multihost.npy"):
            os.remove("/tmp/demo_multihost.npy")
        procs = [
            subprocess.Popen(
                [sys.executable, script, str(r), "2", f"localhost:{port}"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=REPO,
            )
            for r in (0, 1)
        ]
        outs = [p.communicate(timeout=420)[0] for p in procs]
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {r}:\n{out[-1500:]}"
            assert f"[{r}] done" in out, out[-1500:]
        # both ranks computed identical global statistics
        line0 = [l for l in outs[0].splitlines() if "kmeans inertia" in l][0]
        line1 = [l for l in outs[1].splitlines() if "kmeans inertia" in l][0]
        assert line0.split("]")[1] == line1.split("]")[1]
