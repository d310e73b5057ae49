"""Test harness bootstrap.

The reference validates distribution by re-running its whole suite under
``mpirun -n {1..8}`` (reference Jenkinsfile:19-27). The TPU-native analog is
one run against a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``), which exercises every
sharding/collective path without TPU hardware (SURVEY §4). The device count
can be swept via ``HEAT_TPU_TEST_DEVICES`` (default 8 — deliberately not a
divisor-friendly power for every shape, so tail-padding paths are hit).
"""

import os

_n = os.environ.get("HEAT_TPU_TEST_DEVICES", "8")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + f" --xla_force_host_platform_device_count={_n}").strip()
if "xla_backend_optimization_level" not in _flags:
    # The suite is XLA-CPU-compile-bound (one fresh compile per distinct
    # program, plus the per-module cache clear below). LLVM -O0 codegen is
    # semantics-preserving and cuts compile-heavy files by ~35% (test_linalg
    # 113s -> 72s), which is what lets the full sweep fit the tier-1 budget
    # now that the shard_map suites actually execute. Override by setting
    # the flag explicitly in XLA_FLAGS.
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = _flags
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

# The suite is compile-bound and clears JAX's in-memory caches after every
# module; the on-disk cache (at $JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache) turns every recompile of a program some module or
# an earlier run already built into a read.
from heat_tpu.core import program_cache as _program_cache

_program_cache.enable_persistent_cache()


import pytest


@pytest.fixture(autouse=True, scope="module")
def _bound_jax_memory():
    """Long single-process sweeps accumulate XLA executables; clearing the
    caches per module bounds RSS on small CI hosts (a 3-device full-suite
    pass died in a compile-time C++ abort from memory exhaustion without
    this). Costs some re-compiles across modules — correctness unaffected."""
    yield
    import gc

    gc.collect()  # drop dead Array refs BEFORE the cache clear: clearing
    # executables that still have (garbage) references aborts in the XLA
    # CPU client on this host at some module compositions (3-device
    # sweeps; r4 saw the same class of abort without any clearing)
    jax.clear_caches()
