"""Program-cache regression tests (ISSUE 3).

The contract under test: the *second* identical distributed op compiles
**zero** new XLA programs — steady-state dispatch is a registry lookup.
PR 1's :class:`heat_tpu.telemetry.CompileWatcher` is the oracle: it
accumulates the XLA backend-compile durations that fire inside a window,
so a second call that still compiles is caught regardless of where the
compile happens (jit, eager op, or device_put).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import telemetry as tm
from heat_tpu.core import program_cache as pc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _watch(fn):
    """Run ``fn`` under a CompileWatcher; return (result, backend_seconds)."""
    with tm.CompileWatcher() as w:
        out = fn()
        jax.block_until_ready(jax.tree.leaves(out))
    return out, w.stages.get("backend_compile_duration", 0.0)


class TestZeroRecompile:
    """Second identical op → zero new XLA compiles + registry hits."""

    def _assert_second_run_free(self, make_input, op, site):
        a = make_input(0)
        _watch(lambda: op(a))  # warm: compiles + populates the registry
        before = pc.stats()
        b = make_input(1)  # fresh data, identical layout
        out, compile_secs = _watch(lambda: op(b))
        after = pc.stats()
        assert compile_secs == 0.0, (
            f"second {site} call still backend-compiled "
            f"({compile_secs:.4f}s)"
        )
        assert (
            after["sites"][site]["hits"] > before["sites"].get(site, {}).get("hits", 0)
        ), f"no registry hit recorded for {site}: {after['sites']}"
        return out

    def test_resplit(self):
        def make(seed):
            return ht.array(
                np.random.RandomState(seed).rand(7, 5).astype(np.float32),
                split=0,
            )

        out = self._assert_second_run_free(
            make, lambda a: a.resplit(1), "relayout"
        )
        assert out.split == 1

    def test_reshape_split_crossing(self):
        def make(seed):
            return ht.array(
                np.random.RandomState(seed).rand(6, 4).astype(np.float32),
                split=0,
            )

        out = self._assert_second_run_free(
            make, lambda a: a.reshape((24,)), "reshape_split"
        )
        assert out.shape == (24,)

    def test_concatenate_along_split(self):
        def make(seed):
            r = np.random.RandomState(seed)
            return (
                ht.array(r.rand(9).astype(np.float32), split=0),
                ht.array(r.rand(5).astype(np.float32), split=0),
            )

        out = self._assert_second_run_free(
            make, lambda ab: ht.concatenate(ab, axis=0), "concat_split"
        )
        assert out.shape == (14,)

    def test_fancy_index_gather(self):
        idx = np.array([3, 0, 9, 9, 4])

        def make(seed):
            return ht.array(
                np.random.RandomState(seed).rand(11, 3).astype(np.float32),
                split=0,
            )

        out = self._assert_second_run_free(
            make, lambda a: a[ht.array(idx)], "sharded_take"
        )
        assert out.shape == (5, 3)

    def test_factories_is_split(self):
        # single-controller is_split wraps the local block as the global
        # array (no registry site), but the zero-recompile contract still
        # holds: the second identical assembly compiles nothing
        def make(seed):
            return np.random.RandomState(seed).rand(6, 3).astype(np.float32)

        a = ht.array(make(0), is_split=0)
        _watch(lambda: a.larray)
        b_np = make(1)
        out, compile_secs = _watch(lambda: ht.array(b_np, is_split=0).larray)
        assert compile_secs == 0.0
        assert tuple(out.shape) == tuple(a.larray.shape)


class TestRegistry:
    def test_hits_misses_and_reuse(self):
        pc.reset()
        calls = []

        def build():
            calls.append(1)
            return lambda x: x * 2.0

        f1 = pc.cached_program("t_unit", ("a",), build)
        f2 = pc.cached_program("t_unit", ("a",), build)
        f3 = pc.cached_program("t_unit", ("b",), build)
        assert f1 is f2 and f1 is not f3
        assert len(calls) == 2
        s = pc.stats()
        assert s["sites"]["t_unit"] == {"hits": 1, "misses": 2}
        assert float(f1(jnp.float32(3.0))) == 6.0

    def test_env_size_knob_evicts_lru(self, monkeypatch):
        pc.reset()
        monkeypatch.setenv("HEAT_TPU_PROGRAM_CACHE", "2")
        for k in ("a", "b", "c"):
            pc.cached_program("t_lru", k, lambda: (lambda x: x))
        s = pc.stats()
        assert s["size"] <= 2
        assert s["evictions"] >= 1
        # "a" was evicted: re-requesting it is a miss (rebuild)
        before = s["misses"]
        pc.cached_program("t_lru", "a", lambda: (lambda x: x))
        assert pc.stats()["misses"] == before + 1

    def test_donation_separates_programs_and_invalidates_source(self):
        pc.reset()
        x = ht.array(np.arange(35, dtype=np.float32).reshape(7, 5), split=0)
        y = x.resplit(1)  # non-donating out-of-place program
        src = x.larray
        x.resplit_(1)  # donating in-place program
        s = pc.stats()["sites"]["relayout"]
        # same layout signature, but the donating program is a distinct
        # registry entry (donation is part of the key)
        assert s["misses"] >= 2
        np.testing.assert_array_equal(
            x.numpy(), np.arange(35, dtype=np.float32).reshape(7, 5)
        )
        np.testing.assert_array_equal(y.numpy(), x.numpy())
        # the donated source buffer is dead to the framework either way;
        # where the backend supports aliasing it is deleted outright
        if src.is_deleted():
            with pytest.raises(RuntimeError):
                np.asarray(src)

    def test_donation_cannot_kill_copies(self):
        """`ht.array(a)` (copy=True) and `rot90(a, k=0)` must be real
        buffer copies: a later donating resplit_ of the source must not
        invalidate them (on aliasing backends the donated buffer dies)."""
        a = ht.array(np.arange(64, dtype=np.float32).reshape(8, 8), split=0)
        b = ht.array(a)  # copy=True default
        r0 = ht.rot90(a, k=0)
        assert b.larray is not a.larray
        assert r0.larray is not a.larray
        a.resplit_(1)
        np.testing.assert_array_equal(
            b.numpy(), np.arange(64, dtype=np.float32).reshape(8, 8)
        )
        np.testing.assert_array_equal(r0.numpy(), b.numpy())

    def test_no_global_donation_warning_filter(self):
        """The donation-noise suppression is scoped to framework donating
        programs — `import heat_tpu` must NOT install a process-global
        filter that would hide the diagnostic from user code (review
        finding). Checked in a clean subprocess: the parent pytest
        process carries its own pyproject filter for the same message."""
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
            PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
        )
        script = (
            "import warnings, heat_tpu\n"
            "bad = [f for f in warnings.filters\n"
            "       if f[1] is not None and 'donated buffers' in f[1].pattern]\n"
            "assert not bad, bad\n"
            "print('clean')\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", script], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=300,
        )
        assert r.returncode == 0, r.stdout + r.stderr

    def test_donated_source_leaves_live_memory(self, tmp_path):
        """Memory-watermark verification (ISSUE 3): after a donating
        resplit_ the source buffer no longer counts toward live bytes —
        only the relaid-out result remains."""
        n = 1 << 12
        p = ht.get_comm().size
        # feature count divisible by the mesh so the split=1 layout needs
        # no tail pad — source and destination buffers are the same size
        x = ht.array(np.zeros((n, 2 * p), dtype=np.float32), split=0)
        nbytes = x.larray.nbytes
        base = tm.memory.live_bytes()["total"]
        x.resplit_(1)
        jax.block_until_ready(x.larray)
        after = tm.memory.live_bytes()["total"]
        # one buffer's worth, not two (generous slack for small temps)
        assert after - base < nbytes // 2, (base, after, nbytes)

    def test_telemetry_counters_and_trace_events(self, tmp_path):
        pc.reset()
        reg = tm.enable()
        reg.clear()
        try:
            pc.cached_program("t_tel", "k", lambda: (lambda x: x))
            pc.cached_program("t_tel", "k", lambda: (lambda x: x))
            assert reg.counters["program_cache.misses"] == 1
            assert reg.counters["program_cache.hits"] == 1
            assert reg.counters["program_cache.retrace.t_tel"] == 1
            evs = [e for e in reg.events if e["kind"] == "program_cache"]
            assert len(evs) == 1 and evs[0]["event"] == "retrace"
            # summarize() reports the registry block...
            s = tm.report.summarize()
            assert s["program_cache"]["sites"]["t_tel"]["misses"] == 1
            # ...and the Chrome trace exports the retrace as an instant event
            trace = tm.trace.to_trace_events(reg.events)
            marks = [t for t in trace if t.get("cat") == "program_cache"]
            assert marks and marks[0]["ph"] == "i"
            # offline summaries reconstruct retraces from events alone
            s_off = tm.report.summarize(list(reg.events))
            assert s_off["program_cache"]["retraces"] == {"t_tel": 1}
        finally:
            tm.disable()
            reg.clear()

    def test_audit_and_cache_share_signature(self):
        pc.reset()
        from heat_tpu.telemetry import hlo

        hlo.clear()
        x = ht.array(np.arange(24, dtype=np.float32).reshape(6, 4), split=0)
        x.resplit(1, audit=True)
        if x.comm.size <= 1:
            pytest.skip("audit is a no-op on a 1-device mesh")
        rec = hlo.last_audit("resplit")
        assert rec is not None
        # the auditor memoized under the SAME program_key the registry uses
        expected = pc.program_key(
            "relayout", x._relayout_key(1), comm=x.comm
        )
        assert expected in hlo._CACHE


class TestPersistentCompileCache:
    """The one placement rule (program_cache.enable_persistent_cache):
    JAX_COMPILATION_CACHE_DIR set -> JAX uses it and the code sets no
    directory; unset -> <checkout>/.jax_cache. Run in subprocesses: the
    rule reads the environment JAX was imported under."""

    _SCRIPT = (
        "import jax, numpy as np\n"
        "import heat_tpu as ht\n"
        "seen = []\n"
        "real = jax.config.update\n"
        "def spy(name, val):\n"
        "    seen.append(name)\n"
        "    return real(name, val)\n"
        "jax.config.update = spy\n"
        "d = ht.program_cache.enable_persistent_cache()\n"
        "jax.config.update = real\n"
        "x = ht.array(np.arange(10, dtype=np.float32), split=0)\n"
        "float(x.resplit(None).larray[3])\n"
        "print('DIR', d)\n"
        "print('CFG', jax.config.jax_compilation_cache_dir)\n"
        "print('SET_DIR', 'jax_compilation_cache_dir' in seen)\n"
        "print('MIN', jax.config.jax_persistent_cache_min_compile_time_secs)\n"
    )

    def _run(self, cwd, **env_extra):
        env = dict(os.environ)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
            PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
            **env_extra,
        )
        r = subprocess.run(
            [sys.executable, "-c", self._SCRIPT], env=env, cwd=cwd,
            capture_output=True, text=True, timeout=300,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        return dict(
            line.split(" ", 1) for line in r.stdout.splitlines()
            if line.split(" ", 1)[0] in ("DIR", "CFG", "SET_DIR", "MIN")
        )

    def test_variable_set_code_sets_no_directory(self, tmp_path):
        cache = tmp_path / "cc"
        out = self._run(str(tmp_path), JAX_COMPILATION_CACHE_DIR=str(cache))
        assert out["DIR"] == out["CFG"] == str(cache)
        assert out["SET_DIR"] == "False"
        assert float(out["MIN"]) == 0.0
        assert os.listdir(cache), "persistent cache directory stayed empty"

    def test_variable_unset_uses_checkout_cache(self, tmp_path):
        # from a foreign cwd: the path hangs off the checkout, not the cwd
        out = self._run(str(tmp_path))
        assert out["DIR"] == out["CFG"] == os.path.join(REPO, ".jax_cache")
        assert out["SET_DIR"] == "True"
