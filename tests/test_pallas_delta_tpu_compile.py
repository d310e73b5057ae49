"""Compile the gated delta rule's two Pallas kernels at the Qwen3-Next cell's
widths (16 key and 32 value heads of 128, chunks of 64, bfloat16 operands) for
a described TPU v5e: Mosaic accepts what the interpreter ran, also under the
``highest`` default precision that the benchmark's check sets around the rule.
A compile is not a run: nothing here is a time or a result.
"""

import re

import pytest


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("precision", [None, "highest"])
def test_the_rule_and_its_gradients_compile_with_the_kernels_in_the_scans(one_chip, monkeypatch, precision):
    import jax
    import jax.numpy as jnp

    from heat_tpu.nn import gated_delta_rule

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, t, hk, h, d = 1, 1024, 16, 32, 128
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    args = (shape(b, t, hk, d), shape(b, t, hk, d), shape(b, t, h, d), shape(b, t, h), shape(b, t, h))
    loss = lambda *a: jnp.sum(gated_delta_rule(*a, chunk=64, dtype=jnp.bfloat16))  # noqa: E731
    with jax.default_matmul_precision(precision):
        text = jax.jit(jax.grad(loss, argnums=range(5))).lower(*args).compile().as_text()
    for kernel in ("delta_chunk_fwd", "delta_chunk_bwd"):
        assert re.search(rf"%{kernel}\S* = .*custom-call\(", text), kernel
    loops = [l for l in text.splitlines() if re.match(r"\s*%while(\.\d+)? = ", l)]
    assert len([l for l in loops if f"f32[{b},{h},{d},{d}]" in l]) == 2  # the scan and its transpose carry the state
    assert f"f32[{t // 64},{b},{h},{d},{d}]" in text  # one state a chunk
    assert ",64,64]" not in text  # nothing chunk x chunk outside the kernels
