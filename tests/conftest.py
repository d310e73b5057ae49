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


# Tier-1's time is a budget, kept here. The driver runs the suite under a time
# limit with six xdist workers and ``--dist loadfile``: a file is one unit of
# work, and a long file that starts last runs on alone while five workers
# stand idle. So the long files start first: those that compile or run a whole
# train step (by name), then the model files below, longest first. Measured
# 2026-10-02 (junits of the driver's command, PERF.md "Tier-1's budget"): the
# thirteen longest files are all of the rule or the list (174 to 595 s each
# from an empty compile cache, 4,000 of the suite's 6,000 s summed) and no
# other file takes more than 115; the wall is then within 4% of an even sixth
# of the sum. xdist's own reorder, by a file's number of cases, puts the
# compile files (4 cases, 240 to 300 s each) at the tail: 6% longer from an
# empty cache, 18% with a warm one.
_LONG_BY_NAME = ("_tpu_compile", "_step")
_LONG_MODEL_FILES = ("test_trinity", "test_glm", "test_qwen3_next", "test_lfm2", "test_ouro")


def _start_rank(item):
    name = item.path.stem
    if name.endswith(_LONG_BY_NAME):
        return 0
    if name in _LONG_MODEL_FILES:
        return 1 + _LONG_MODEL_FILES.index(name)
    return 1 + len(_LONG_MODEL_FILES)


def pytest_configure(config):
    # the ``loadfile`` queue is then the collection's order, which the hook below sets
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    # stable: a file's cases stay together and as pytest left them (a parametrised module fixture's cases grouped)
    items.sort(key=_start_rank)


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
    # the telemetry registry is the process's own: a file that reads a mean over "the steps the process made"
    # (tests/chipbench/test_chipbench_qnext_step.py: ``qnext_held_load``) must not depend on which file its worker ran before
    from heat_tpu import telemetry

    telemetry.get_registry().clear()


@pytest.fixture(scope="module")
def topo():
    """A described TPU v5e host (``v5e:2x2``) to compile for with no chip
    attached; the module's tests are skipped where libtpu cannot describe one.
    Only a test that asks for it loads the library. The benchmark's files
    under ``tests/chipbench/`` keep a ``topo`` of their own, which shadows
    this one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
