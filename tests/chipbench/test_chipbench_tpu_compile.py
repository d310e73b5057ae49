"""Compile the cells' programs at full size for a described TPU v5e and see
from the compiler's memory analysis that they fit. A compile is not a run:
nothing here is a time or a result.

The only test file that loads the TPU's compiler. ``KMeans.fit`` and
``cdist`` ask ``jax.default_backend()`` and would take their CPU branch in
the sandbox, so the tests lower the jitted functions those dispatch to on a
TPU, on shapes placed on the described devices.
"""

import os

import pytest

from chipbench import manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
USABLE = 15.75 * 2**30  # of a v5e chip's 16 GiB, what the runtime leaves a program


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


@pytest.fixture(scope="module")
def parts():
    return manifest.load(REPO)


def _total(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes


def _lloyd_one_chip(topo, config, rows):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from heat_tpu.cluster.pallas_lloyd import lloyd_fit_pallas

    s = SingleDeviceSharding(topo.devices[0])
    k, d = config["n_clusters"], config["features"]
    return lloyd_fit_pallas.lower(
        jax.ShapeDtypeStruct((rows, d), jnp.float32, sharding=s),
        jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=s),
        rows, config["max_iter"], jax.ShapeDtypeStruct((), jnp.float32, sharding=s),
    ).compile()


def test_kmeans_one_chip_fits(topo, parts):
    cell = parts.cell("kmeans-fit-1chip")
    config = parts.config(cell)
    compiled = _lloyd_one_chip(topo, config, config["rows"])
    assert "tpu_custom_call" in compiled.as_text()
    assert 0.25 * 16e9 < _total(compiled) < USABLE


def test_kmeans_at_twice_the_rows_is_refused(topo, parts):
    """What ``reduced`` says of the rows: at 2^25 the kernel's operand is
    copied into a layout that pads 64 features to 128 lanes, 16 GiB."""
    config = parts.config(parts.cell("kmeans-fit-1chip"))
    with pytest.raises(Exception, match="(?i)hbm|memory|exhausted"):
        _lloyd_one_chip(topo, config, 2 * config["rows"])


def test_kmeans_four_chips_fits_on_each(topo, parts):
    import jax
    import jax.numpy as jnp

    from heat_tpu.cluster.pallas_lloyd import lloyd_fit_pallas_sharded
    from heat_tpu.core.communication import MeshCommunication

    config = parts.config(parts.cell("kmeans-fit-4chip"))
    comm = MeshCommunication(devices=topo.devices)
    rows, k, d = config["rows"], config["n_clusters"], config["features"]
    compiled = lloyd_fit_pallas_sharded.lower(
        comm,
        jax.ShapeDtypeStruct((rows, d), jnp.float32, sharding=comm.sharding(0, 2)),
        jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=comm.replicated()),
        rows, config["max_iter"],
        jax.ShapeDtypeStruct((), jnp.float32, sharding=comm.replicated()),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert 0.25 * 16e9 < _total(compiled) < USABLE  # bytes on each device


def test_cdist_one_chip_fits(topo, parts):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from heat_tpu.spatial.pallas_cdist import _euclid_pallas_jit

    config = parts.config(parts.cell("cdist-susy-1chip"))
    s = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((config["rows"], config["features"]), jnp.float32, sharding=s)
    compiled = _euclid_pallas_jit.lower(x, x, 0.0, epilogue="dist", precision="bf16x3").compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= config["rows"] ** 2 * 4
    # the padded kernel output and its sliced copy are both live: the call
    # loop must hold no earlier result
    assert 0.25 * 16e9 < _total(compiled) < USABLE
    assert _total(compiled) + out > USABLE
