"""The harness end to end on the CPU, through the tiny manifest beside this
file: every part of it (configurations, a kind, a traffic mix, a metric, a
count, a peaks table) is a file the harness has never named.

A CPU run rehearses control flow and the decision of ``correct``; none of
its numbers is a device metric.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench import limits, manifest, run

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(autouse=True)
def _default_comm_again():
    yield
    import heat_tpu as ht

    ht.use_comm(None)  # the harness sets the cell's own mesh as the default


def _run(capsys, workload, trace, seed=4000000007, seconds=0.3):
    rc = run.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        root=TINY,
    )
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return rc, lines


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", ["tiny-kmeans", "tiny-kmeans-4", "tiny-cdist", "tiny-colsum"])
def test_a_run_prints_the_contracts_line(capsys, workload, trace):
    rc, lines = _run(capsys, workload, trace)
    assert rc == 0
    last = lines[-1]
    assert set(last) == LINE_KEYS | ({"breakdown"} if trace else set())
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    parts = manifest.load(TINY)
    cell = parts.cell(workload)
    declared = {m["name"]: m["unit"] for m in parts.metrics("per_layer" if trace else "end_to_end", cell)}
    assert set(last["metrics"]) <= set(declared)
    for name, m in last["metrics"].items():
        assert m["unit"] == declared[name] and isinstance(m["value"], float)
    assert last["device"]["count"] == cell["chips"] and last["device"]["platform"] == "cpu"
    compared = [l for l in lines if "compared" in l]
    assert compared and all({"value", "limit", "ok"} <= set(l) for l in compared)
    assert any("samples" in l for l in lines)
    if trace:
        assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
        assert len(last["breakdown"]["device_ops"]) <= 10 and len(last["breakdown"]["idle_gaps"]) <= 10
        assert last["metrics"]["compiles_in_window.fit"]["value"] == 0
        # a reader that finds nothing to read (no program of that name in a
        # CPU trace) leaves its metric out of the line
        assert "lloyd_roofline" not in last["metrics"]
    else:
        assert set(last["metrics"]) == set(declared)


def test_the_same_seed_gives_the_same_inputs():
    parts = manifest.load(TINY)
    ref = parts.module("references", "lloyd")
    import jax
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("r",))
    a = ref.make_mixture(2**31 + 11, 512, 8, 4, 0.25, 128, mesh, "r")
    b = ref.make_mixture(2**31 + 11, 512, 8, 4, 0.25, 128, mesh, "r")
    c = ref.make_mixture(2**31 + 12, 512, 8, 4, 0.25, 128, mesh, "r")
    assert a.shape == (1024, 8) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    one = ref.make_mixture(2**31 + 11, 512, 8, 4, 0.25, 128, Mesh(np.asarray(jax.devices()[:1]), ("r",)), "r")
    assert np.array_equal(np.asarray(a)[:512], one), "shard 0 is the one-chip data"


def _break_kmeans_step(monkeypatch):
    """A fit whose iterations return their state unchanged."""
    from heat_tpu.cluster import kmeans

    real = kmeans._lloyd_fit

    def lazy(xb, w, centers, max_iter, tol):
        _, labels, inertia, _ = real(xb, w, centers, 0, tol)
        return centers, labels, inertia, max_iter

    monkeypatch.setattr(kmeans, "_lloyd_fit", lazy)


def _break_kmeans_exchange(monkeypatch):
    """A fit that sees only the first quarter of the rows (one shard's
    part, as when the exchange between chips is left out)."""
    from heat_tpu.cluster import kmeans

    real = kmeans._lloyd_fit

    def partial(xb, w, centers, max_iter, tol):
        import jax.numpy as jnp

        part = w * (jnp.arange(w.shape[0]) < w.shape[0] // 4)
        c, _, _, n = real(xb, part, centers, max_iter, tol)
        _, labels, inertia, _ = real(xb, w, c, 0, tol)
        return c, labels, inertia, n

    monkeypatch.setattr(kmeans, "_lloyd_fit", partial)


def _break_cdist_answer(monkeypatch):
    """A distance altered where it is produced."""
    from heat_tpu.spatial import distance

    real = distance._local_dist
    monkeypatch.setattr(distance, "_local_dist", lambda *a: real(*a) + 1e-3)


@pytest.mark.parametrize(
    "workload,breaker",
    [("tiny-kmeans", _break_kmeans_step), ("tiny-kmeans-4", _break_kmeans_exchange),
     ("tiny-cdist", _break_cdist_answer)],
    ids=["step-returns-state-unchanged", "part-of-the-rows-left-out", "answer-altered"],
)
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch, workload, breaker):
    breaker(monkeypatch)
    rc, lines = _run(capsys, workload, 0)
    assert rc == 0
    assert lines[-1]["correct"] is False and lines[-1]["failed"] >= 1
    assert any(l.get("ok") is False for l in lines)


@pytest.mark.parametrize("workload", ["tiny-kmeans", "tiny-cdist"])
def test_the_lower_precision_control_fails_the_comparison(capsys, workload):
    """The reference computed with single-pass bfloat16 products, put in the
    program's place, has to fail a limit on every seed; the program passes
    all of them."""
    assert limits.main(["--workload", workload, "--seeds", "1,2,4000000007"], root=TINY) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    lim = manifest.load(TINY).config(manifest.load(TINY).cell(workload))["limits"]
    assert len(rows) == 3
    for row in rows:
        assert all(row["program"][k] <= lim[k] for k in row["program"])
        assert any(row["control"][k] > lim[k] for k in row["control"])


def _command(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"), "--workload", workload,
         "--seed", "3000000019", "--seconds", "0.3", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_the_run_command_itself_on_the_tiny_manifest():
    done = _command(TINY, "tiny-colsum")
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(last) == LINE_KEYS and last["correct"] is True
    assert set(last["metrics"]) == {"call_p50_ms", "items_per_s", "setup_s"}


def test_a_real_cell_refuses_a_cpu():
    done = _command(REPO, "kmeans-fit-1chip")
    assert done.returncode == run.NO_DEVICE
    assert not [l for l in done.stdout.splitlines() if l.startswith("{")]
    assert "peaks table" in done.stderr
