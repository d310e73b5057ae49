"""The device contract of the launchers (ISSUE 21), as far as a CPU host
can check it: a measurement path that finds no chip fails instead of
carrying on; "tpu" means the TPU; peaks come from one table and an unknown
chip is an error; a chip belongs to one process."""

import json
import os
import subprocess
import sys

import pytest

import heat_tpu as ht
from heat_tpu.core.devices import Device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *argv, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script), *argv],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
    )


class TestNoChipFails:
    def test_chip_smoke_names_the_missing_chip(self):
        r = _run("chip_smoke.py")
        assert r.returncode != 0
        assert r.stdout.strip() == "", "printed a result without a chip"
        assert "no TPU" in r.stderr and "'cpu'" in r.stderr

    def test_bench_without_small_names_the_missing_chip(self):
        r = _run("bench.py")
        assert r.returncode != 0
        assert r.stdout.strip() == "", "printed a result without a chip"
        assert "no TPU" in r.stderr and "--small" in r.stderr

    @pytest.mark.slow
    def test_chip_smoke_rehearsal_passes_and_says_so(self):
        r = _run("chip_smoke.py", "--rehearse-cpu", timeout=900)
        assert r.returncode == 0, r.stderr[-3000:]
        lines = [json.loads(l) for l in r.stdout.splitlines() if l.startswith("{")]
        stages = [l for l in lines if "stage" in l]
        assert [l["stage"] for l in stages] == [
            "device", "train", "array", "kernels", "serve"
        ]
        assert all(l["ok"] and l["rehearsal"] for l in stages)
        assert lines[-1]["ok"] and lines[-1]["rehearsal"]
        assert lines[-1]["device"]["platform"] == "cpu"


class TestDevices:
    def test_tpu_means_the_tpu(self):
        with pytest.raises(RuntimeError, match="tpu"):
            Device("tpu").jax_devices()
        with pytest.raises(RuntimeError, match="tpu"):
            ht.array([1.0, 2.0], device="tpu")

    def test_default_device_is_the_default_backend(self):
        assert ht.get_device() == ht.cpu

    def test_peak_table_is_keyed_by_device_kind(self):
        v5e = ht.chip_peaks("TPU v5 lite")
        assert (v5e.bf16_flops, v5e.int8_ops) == (197e12, 393e12)
        assert (v5e.hbm_bytes_per_s, v5e.hbm_bytes) == (819e9, 16e9)

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", "tpu v5 lite", ""])
    def test_unknown_device_kind_is_an_error(self, kind):
        with pytest.raises(ValueError, match="no published peaks"):
            ht.chip_peaks(kind)


class TestOneProcessPerChip:
    def test_default_replica_pool_is_refused_on_a_tpu_host(
        self, tmp_path, monkeypatch
    ):
        import jax

        from heat_tpu.serve.net import ReplicaPool

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="one process"):
            ReplicaPool(str(tmp_path / "ckpt"), 2, log_dir=str(tmp_path))
        # virtual-CPU-mesh pools are unchanged
        pool = ReplicaPool(str(tmp_path / "ckpt"), 2, mesh=2,
                           log_dir=str(tmp_path))
        assert pool.replicas == []

    def test_cpu_pinned_replicas_are_not_refused(self, tmp_path, monkeypatch):
        import jax

        from heat_tpu.serve.net import ReplicaPool

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ReplicaPool(str(tmp_path / "ckpt"), 1, env={"JAX_PLATFORMS": "cpu"},
                    log_dir=str(tmp_path))
