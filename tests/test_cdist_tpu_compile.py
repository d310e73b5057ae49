"""Compile the local distance program, ``_local_dist``, for a described
TPU v5e and read, from the compiled text and the compiler's memory
analysis, that ``cdist`` and ``rbf`` each write the result once: one
result-sized output fusion, no result-sized ``copy``, ``slice``, ``pad`` or
second fusion, next to no temporaries, and on a mesh a slab a chip with no
collective. A compile is not a run: nothing here is a time or a result.

The tests lower the jitted function the public calls launch, on shapes
placed on the described devices: there is no device here to hold an array.
"""

import re

import numpy as np
import pytest

GIB = 2**30
MIB = 2**20
HALF_A_CHIP = 8 * GIB  # of a v5e chip's 16 GiB
GAMMA = {"dist": None, "rbf": np.float32(0.5)}




def _lower(m, n, k, epilogue, x_sharding, y_sharding):
    import jax
    import jax.numpy as jnp

    from heat_tpu.spatial.distance import _local_dist, _quadratic_euclidean

    return _local_dist.lower(
        _quadratic_euclidean,
        jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=x_sharding),
        jax.ShapeDtypeStruct((n, k), jnp.float32, sharding=y_sharding),
        jnp.float32,
        GAMMA[epilogue],
    ).compile()


def _one_chip(topo, m, n, k, epilogue="dist"):
    from jax.sharding import SingleDeviceSharding

    s = SingleDeviceSharding(topo.devices[0])
    return _lower(m, n, k, epilogue, s, s)


def _four_chips(topo, rows, k):
    """``cdist`` on a mesh: x split over the rows, y whole on every chip,
    each chip writing its (rows / 4, rows) slab."""
    from heat_tpu.core.communication import MeshCommunication

    comm = MeshCommunication(devices=topo.devices)
    return _lower(rows, rows, k, "dist", comm.sharding(0, 2), comm.replicated())


def _result_sized(text, m, n):
    """Instructions of the compiled program's entry computation (what the
    chip launches; a fusion's body is one of them) that produce a float32
    array with at least the result's rows and columns, by opcode: the one
    output fusion is the only one the program may hold."""
    shaped = re.compile(r"= f32\[(\d+),(\d+)\]\S* ([\w-]+)\(")
    entry = text[text.index("\nENTRY "):]
    found = []
    for line in entry[:entry.index("\n}")].splitlines():
        hit = shaped.search(line)
        if hit and int(hit.group(1)) >= m and int(hit.group(2)) >= n:
            found.append(hit.group(3))
    return found


def _total(compiled):
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes


COLLECTIVES = re.compile(
    r"\b(all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter)"
)


@pytest.mark.parametrize("epilogue", ["dist", "rbf"])
def test_one_chip_writes_the_matrix_and_nothing_else(topo, epilogue):
    rows, k = 40_000, 18  # SUSY's, the cell's
    compiled = _one_chip(topo, rows, rows, k, epilogue)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    # the epilogue rides in the same fusion: ``rbf`` is no second pass
    assert _result_sized(text, rows, rows) == ["fusion"]
    assert ("exponential" in text) == (epilogue == "rbf")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < MIB
    assert mem.output_size_in_bytes >= rows * rows * 4
    assert _total(compiled) < HALF_A_CHIP


def test_a_chip_of_four_writes_its_slab_and_nothing_else(topo):
    rows, k = 40_000, 18
    compiled = _four_chips(topo, rows, k)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert not COLLECTIVES.search(text)
    assert _result_sized(text, rows // 4, rows) == ["fusion"]
    assert f"f32[{rows // 4},{rows}]" in text
    mem = compiled.memory_analysis()  # bytes on each device
    assert mem.temp_size_in_bytes < MIB
    assert mem.output_size_in_bytes >= rows // 4 * rows * 4
    assert _total(compiled) < HALF_A_CHIP // 4


@pytest.mark.parametrize(
    "m,n,k",
    [
        (5, 3, 2),           # smaller than a tile on both axes
        (130, 257, 33),      # non-multiples everywhere
        (1000, 2500, 18),    # chip_smoke's ragged pair
        (40_000, 13, 18),    # many rows beside a sliver of lanes
        (2048, 16384, 128),  # tile multiples everywhere
    ],
)
def test_other_shapes_compile(topo, m, n, k):
    compiled = _one_chip(topo, m, n, k)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    root = [line for line in text.splitlines() if "ROOT" in line and f"f32[{m},{n}]" in line]
    assert root
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * MIB
