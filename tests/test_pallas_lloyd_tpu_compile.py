"""Compile the Pallas Lloyd fit for a described TPU v5e and read, from the
compiled text and the compiler's memory analysis, that the kernel takes X
as it lies on the chip: no X-sized ``copy`` or ``transpose``, and
temporaries far under X's own size. A compile is not a run: nothing here
is a time or a result.

``KMeans.fit`` asks ``jax.default_backend()`` and would take its CPU branch
in the sandbox, so the tests lower the jitted fits it dispatches to on a
TPU, on shapes placed on the described devices.
"""

import re

import pytest

GIB = 2**30
USABLE = 15.75 * GIB  # of a v5e chip's 16 GiB, what the runtime leaves a program




def _one_chip(topo, rows, d, k=8, max_iter=30):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from heat_tpu.cluster.pallas_lloyd import lloyd_fit_pallas

    s = SingleDeviceSharding(topo.devices[0])
    return lloyd_fit_pallas.lower(
        jax.ShapeDtypeStruct((rows, d), jnp.float32, sharding=s),
        jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=s),
        rows, max_iter, jax.ShapeDtypeStruct((), jnp.float32, sharding=s),
    ).compile()


def _four_chips(topo, rows, d, k=8, max_iter=30):
    import jax
    import jax.numpy as jnp

    from heat_tpu.cluster.pallas_lloyd import lloyd_fit_pallas_sharded
    from heat_tpu.core.communication import MeshCommunication

    comm = MeshCommunication(devices=topo.devices)
    return lloyd_fit_pallas_sharded.lower(
        comm,
        jax.ShapeDtypeStruct((rows, d), jnp.float32, sharding=comm.sharding(0, 2)),
        jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=comm.replicated()),
        rows, max_iter,
        jax.ShapeDtypeStruct((), jnp.float32, sharding=comm.replicated()),
    ).compile()


def _relayouts_of_x(text, rows, d):
    """Lines of the compiled text in which a ``copy`` or a ``transpose``
    produces an array of X's size, in either orientation (a bitcast is
    free and is not one)."""
    shaped = re.compile(rf"= f32\[({rows},{d}|{d},{rows})\]\S* (copy|transpose)\(")
    return [line.strip()[:160] for line in text.splitlines() if shaped.search(line)]


def _kernels(text):
    """The Mosaic calls of a compiled text, by the ``pallas_call``'s name."""
    return sorted(set(re.findall(r"%(lloyd_\w+?)(?:\.\d+)? = ", text)))


def _as_large_as(text, rows, least):
    """Lines that define an array of ``rows`` (in either orientation) times
    at least ``least`` elements a row, whatever its type: the (n, 8)
    distances, lane-padded or not, or a second X."""
    shaped = re.compile(rf"= \w+\[(?:{rows},(\d+)|(\d+),{rows})\]\S* (?!parameter|bitcast|get-tuple-element)")
    return [
        line.strip()[:160] for line in text.splitlines()
        for m in [shaped.search(line)] if m and int(m.group(1) or m.group(2)) >= least
    ]


# memory_analysis' temporaries of the fits at PR 49's parent (one chip, a chip
# of four): XLA's final pass held the (n, 8) distances and a column of norms
PARENT_TEMPORARIES = (604_205_568, 604_237_824)


def test_one_chip_takes_x_as_it_lies(topo):
    """The whole fit is 4,362,086,400 bytes on the described chip (X
    4,294,967,296, int32 labels 67,108,864, no temporaries; 5,033,400,832
    with XLA's final pass, PR 49's parent)."""
    rows, d = 2**24, 64
    compiled = _one_chip(topo, rows, d)
    text = compiled.as_text()
    assert _kernels(text) == ["lloyd_assign", "lloyd_update"]
    assert _relayouts_of_x(text, rows, d) == []
    assert _as_large_as(text, rows, 2) == []  # no f32[16777216,8], no second X
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes <= PARENT_TEMPORARIES[0] and m.temp_size_in_bytes < 1 * GIB
    assert m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes < 4.1 * GIB


def test_a_chip_of_four_takes_its_shard_as_it_lies(topo):
    rows, d = 2**26, 64
    compiled = _four_chips(topo, rows, d)
    text = compiled.as_text()
    assert _kernels(text) == ["lloyd_assign", "lloyd_update"] and "all-reduce" in text
    assert _relayouts_of_x(text, rows // 4, d) == []
    assert _as_large_as(text, rows // 4, 2) == []
    m = compiled.memory_analysis()  # a chip
    assert m.temp_size_in_bytes <= PARENT_TEMPORARIES[1] and m.temp_size_in_bytes < 1 * GIB


def test_heats_own_rows_fit_one_chip(topo):
    """2^25 x 64, Heat's own size (8 GiB): refused while the kernel's
    operand was a lane-padded copy (16 GiB of temporaries)."""
    m = _one_chip(topo, 2**25, 64).memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    assert 8 * GIB < total < USABLE


@pytest.mark.parametrize("d", [18, 100, 128])
def test_no_relayout_at_other_widths(topo, d):
    # feature-major for 18 (SUSY's) and 100, the row-major kernel for 128
    rows = 2**20
    compiled = _one_chip(topo, rows, d)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _relayouts_of_x(text, rows, d) == []
    assert compiled.memory_analysis().temp_size_in_bytes < rows * d * 4


def test_many_clusters_compile_in_the_feature_major_form(topo):
    # k over 128 on sublanes: 32 tiles of scores a block, inside VMEM
    compiled = _one_chip(topo, 2**20, 64, k=256)
    assert "tpu_custom_call" in compiled.as_text()
