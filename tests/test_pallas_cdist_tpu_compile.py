"""Compile the Pallas cdist kernel for a described TPU v5e and read, from
the compiled text and the compiler's memory analysis, that the kernel
writes the distance matrix at its own shape: no result-sized ``slice``,
``copy``, ``pad`` or ``fusion`` beside the kernel, next to no temporaries,
and one result in the program's memory. A compile is not a run: nothing
here is a time or a result.

``cdist`` asks ``jax.default_backend()`` and would take its CPU branch in
the sandbox, so the tests lower the jitted kernel it dispatches to on a
TPU, on shapes placed on the described devices.
"""

import os
import re

import pytest

GIB = 2**30
MIB = 2**20
HALF_A_CHIP = 8 * GIB  # of a v5e chip's 16 GiB


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
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


def _one_chip(topo, m, n, k, epilogue="dist"):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from heat_tpu.spatial.pallas_cdist import _euclid_pallas_jit

    s = SingleDeviceSharding(topo.devices[0])
    return _euclid_pallas_jit.lower(
        jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=s),
        jax.ShapeDtypeStruct((n, k), jnp.float32, sharding=s),
        0.5, epilogue=epilogue, precision="bf16x3",
    ).compile()


def _four_chips(topo, rows, k):
    """The slab path of ``cdist`` on a mesh: x split over the rows, y whole
    on every chip, each chip writing its (rows / 4, rows) slab."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.communication import MeshCommunication
    from heat_tpu.spatial.distance import _pallas_local

    comm = MeshCommunication(devices=topo.devices)
    return jax.jit(
        lambda x, y: _pallas_local(comm, x, y, "dist", 0.0)
    ).lower(
        jax.ShapeDtypeStruct((rows, k), jnp.float32, sharding=comm.sharding(0, 2)),
        jax.ShapeDtypeStruct((rows, k), jnp.float32, sharding=comm.replicated()),
    ).compile()


def _result_sized(text, m, n):
    """Instructions of the compiled text that produce a float32 array with
    at least the result's rows and columns (the padded form's 40,448 x
    40,960 too), by opcode: the kernel's own custom call is the only one
    the program may hold."""
    shaped = re.compile(r"= f32\[(\d+),(\d+)\]\S* ([\w-]+)\(")
    found = []
    for line in text.splitlines():
        hit = shaped.search(line)
        if hit and int(hit.group(1)) >= m and int(hit.group(2)) >= n:
            found.append(hit.group(3))
    return found


def _total(compiled):
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes


@pytest.mark.parametrize("epilogue", ["dist", "rbf"])
def test_one_chip_writes_the_matrix_and_nothing_else(topo, epilogue):
    rows, k = 40_000, 18  # SUSY's, the cell's: a multiple of neither block
    compiled = _one_chip(topo, rows, rows, k, epilogue)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "euclid_tile" in text
    assert _result_sized(text, rows, rows) == ["custom-call"]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * MIB
    assert mem.output_size_in_bytes >= rows * rows * 4
    assert _total(compiled) < HALF_A_CHIP


def test_a_chip_of_four_writes_its_slab_and_nothing_else(topo):
    rows, k = 40_000, 18
    compiled = _four_chips(topo, rows, k)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "euclid_tile" in text
    assert _result_sized(text, rows // 4, rows) == ["custom-call"]
    mem = compiled.memory_analysis()  # bytes on each device
    assert mem.temp_size_in_bytes < 64 * MIB
    assert mem.output_size_in_bytes >= rows // 4 * rows * 4
    assert _total(compiled) < HALF_A_CHIP // 4


@pytest.mark.parametrize(
    "m,n,k",
    [
        (5, 3, 2),           # one block, larger than the result on both axes
        (130, 257, 33),      # non-multiples everywhere
        (1000, 2500, 18),    # chip_smoke's ragged pair
        (40_000, 13, 18),    # a ragged row block beside a sub-block of lanes
        (2048, 16384, 128),  # block multiples: every block whole, no pad
    ],
)
def test_edge_blocks_compile_at_other_shapes(topo, m, n, k):
    compiled = _one_chip(topo, m, n, k)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the result leaves the kernel: the root is the custom call itself
    root = [line for line in text.splitlines() if "ROOT" in line and "euclid_tile" in line]
    assert root and f"f32[{m},{n}]" in root[0]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * MIB
