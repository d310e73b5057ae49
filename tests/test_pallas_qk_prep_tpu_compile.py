"""Compile the two kernels of ``heat_tpu/nn/pallas_qk_prep.py`` alone at the
Trinity-Mini and Qwen3-Next cells' query and key shapes (float32 projections
to bfloat16, the queries beside their gates) for a described TPU v5e: Mosaic
accepts what the interpreter ran, and from a projection's output laid out
head-major (what XLA does for this consumer) no copy and no transpose stands
round the two calls, only the angles' tables, the sum of the gain's row blocks
and the zeros of the gates' lanes. A kernel, not a step: seconds. A compile is not a run: nothing
here is a time or a result.
"""

import re

import pytest

# (B, T, heads, a head's lanes in the projection), a head, eps, theta, the rotated fraction
SHAPES = {
    "trinity-q": ((1, 16384, 32, 256), 128, 1e-5, 10000.0, 1.0),
    "trinity-q-full-layer": ((1, 16384, 32, 256), 128, 1e-5, None, 1.0),  # the two layers without a window take no positions
    "trinity-k": ((1, 16384, 4, 128), 128, 1e-5, 10000.0, 1.0),
    "qwen3next-q": ((1, 8192, 16, 512), 256, 1e-6, 1e7, 0.25),
    "qwen3next-k": ((1, 8192, 2, 256), 256, 1e-6, 1e7, 0.25),
}


@pytest.mark.parametrize("case", sorted(SHAPES))
def test_both_kernels_lower_to_mosaic_at_the_cells_shapes(topo, case):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from heat_tpu.nn import pallas_qk_prep

    (b, t, h, w), d, eps, theta, fraction = SHAPES[case]
    here = SingleDeviceSharding(topo.devices[0])
    p = pallas_qk_prep.Pass(d, "head", eps, theta, fraction, jnp.bfloat16, pallas_qk_prep.ROWS, False)
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=here)  # noqa: E731

    def both(x, gain, g):
        out, vjp = jax.vjp(lambda x, gain: pallas_qk_prep.qk_prep(x.transpose(0, 2, 1, 3), gain, p), x, gain)
        return out, vjp(g)

    arguments = shape((b, h, t, w), jnp.float32), shape((1, d), jnp.float32), shape((b, h, t, d), jnp.bfloat16)
    text = jax.jit(both).lower(*arguments).compile().as_text()
    for kernel in ("qk_prep_fwd", "qk_prep_bwd"):
        assert len(re.findall(rf"%{kernel}\S* = .*custom-call\(", text)) == 1, kernel
    out, (dx, dgain) = jax.eval_shape(both, *arguments)
    assert (out.shape, out.dtype) == ((b, h, t, d), jnp.bfloat16)  # head-major, as the flash kernels take it
    assert (dx.shape, dx.dtype, dgain.shape) == ((b, h, t, w), jnp.float32, (1, d))
    assert not re.search(r" = \S+ (transpose|copy)\(", text)  # the two transposes are layouts, and nothing is copied
