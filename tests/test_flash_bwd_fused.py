"""The flash backward takes a block pair once wherever the shape lets it:
the rule of ``pallas_attention._flash_bwd_dispatch`` as a function of shapes,
the fused kernel's gradients against the two passes' in the Pallas interpreter
(they sum the same blocks in the same order: equal to the bit), the two
counters, and the kernel compiled alone for a described TPU v5e at two of the
training cells' shapes. A compile is not a run: nothing here is a time.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu.parallel.pallas_attention as pa
from heat_tpu import telemetry
from heat_tpu.parallel import flash_attention

MIB = 2**20

# query heads, key-value heads, positions, head size, window: the five training cells' attention layers
# (`chipbench/configs/*-train.json`; Trinity-Mini's two forms) and one past the chip's VMEM
CELLS = {
    "glm47flash": (20, 20, 8192, 256, None),
    "qwen3next": (16, 2, 8192, 256, None),
    "trinity-full": (32, 4, 16384, 128, None),
    "trinity-window": (32, 4, 16384, 128, 2048),
    "lfm2": (32, 8, 8192, 64, None),
    "olmoe": (16, 16, 4096, 128, None),
    "past-the-vmem": (8, 8, 32768, 256, None),
}


@pytest.mark.parametrize("cell", CELLS)
def test_the_rule_reads_the_shape(cell):
    heads, kv_heads, t, d, window = CELLS[cell]
    block_q, block_k = pa._tiles(window, None, None)
    fused = pa._bwd_takes_fused(t, t, d, 2, block_q, block_k)
    assert fused == (cell != "past-the-vmem")
    # what it reckons: two float32 accumulators and two output blocks of a whole key-value head, and the tiles
    reckoned = pa._fused_bwd_vmem_bytes(t, max(d, 128), 2, block_q, block_k)
    assert reckoned > 2 * t * max(d, 128) * (4 + 2 * 2)
    assert (reckoned <= pa._VMEM_BUDGET_BYTES) == fused
    # the groups' sum is the kernel's own and a window keeps the blocks whole: neither is a term
    assert pa._bwd_takes_fused(t, t, d, 2, 512, 1024) == fused
    # the limit the call asks for stays inside the chip's 128 MiB wherever the rule admits the shape
    assert not fused or reckoned * 5 // 4 <= 128 * MIB


def test_the_old_gate_would_have_refused_three_cells():
    """4 MiB of resident float32 dQ: the form no cell ran (ISSUE 42)."""
    over = [c for c, (_, _, t, d, _) in CELLS.items() if t * max(d, 128) * 4 > 4 * MIB]
    assert over == ["glm47flash", "qwen3next", "trinity-full", "trinity-window", "past-the-vmem"]


def _grads(impl, q, k, v, g, **kw):
    def loss(q_, k_, v_):
        out = flash_attention(q_, k_, v_, bwd_impl=impl, interpret=True, **kw)
        return (out.astype(jnp.float32) * g).sum()

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


CASES = {
    # batch, positions, query heads, key-value heads, head size, dtype, the call's other arguments
    "head-256-bf16": (2, 512, 2, 2, 256, jnp.bfloat16, dict(causal=True, block_q=128, block_k=256)),
    "group-of-4-f32": (1, 384, 8, 2, 64, jnp.float32, dict(causal=True, block_q=128, block_k=128)),
    "group-of-4-window-bf16": (1, 512, 4, 1, 128, jnp.bfloat16, dict(causal=True, window=160, block_q=128, block_k=64)),
    "window-ragged-f32": (1, 300, 4, 2, 32, jnp.float32, dict(causal=True, window=24, block_q=32, block_k=64)),
    "every-block-valid-257-f32": (1, 300, 4, 2, 32, jnp.float32, dict(causal=False, kv_valid=257, block_q=64, block_k=128)),
    # 4,352 positions at a head of 256 pad to a resident block of 5 MiB: over the old gate, at the tuned tiles
    "over-the-old-gate-bf16": (1, 4352, 1, 1, 256, jnp.bfloat16, dict(causal=True)),
}


@pytest.mark.parametrize("case", CASES)
def test_fused_gradients_are_the_two_passes_to_the_bit(case):
    b, t, h, h_kv, d, dtype, kw = CASES[case]
    rng = np.random.default_rng(42)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, heads, d)), dtype) for heads in (h, h_kv, h_kv))
    g = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    count = telemetry.get_registry().counters
    before = count["attn.bwd.fused"], count["attn.bwd.two_pass"]
    two_pass = _grads("two_pass", q, k, v, g, **kw)
    assert (count["attn.bwd.fused"], count["attn.bwd.two_pass"]) == (before[0], before[1] + 1)
    # the default is the rule, and the rule takes the fused kernel at every one of these shapes
    chosen = _grads("auto", q, k, v, g, **kw)
    assert (count["attn.bwd.fused"], count["attn.bwd.two_pass"]) == (before[0] + 1, before[1] + 1)
    for name, a, ref in zip(("dq", "dk", "dv"), chosen, two_pass):
        assert a.shape == ref.shape and a.dtype == ref.dtype
        assert bool((a == ref).all()) and float(jnp.abs(ref.astype(jnp.float32)).max()) > 0, name


def test_the_default_is_the_rule_and_a_forced_path_stays_forced(monkeypatch):
    """`flash_attention`'s default and `TransformerLM`'s four fields say "auto";
    where the rule refuses a shape the two passes run, and "fused" still forces."""
    import inspect

    from heat_tpu.nn import transformer

    assert inspect.signature(flash_attention).parameters["bwd_impl"].default == "auto"
    fields = [cls.flash_bwd_impl for cls in vars(transformer).values() if hasattr(cls, "flash_bwd_impl")]
    assert len(fields) == 4 and set(fields) == {"auto"}

    q = k = v = jnp.ones((1, 256, 2, 64), jnp.float32)
    g = jnp.ones((1, 256, 2, 64), jnp.float32)
    count = telemetry.get_registry().counters
    monkeypatch.setattr(pa, "_VMEM_BUDGET_BYTES", 64 * 1024)  # a chip this shape is past
    before = count["attn.bwd.fused"], count["attn.bwd.two_pass"]
    _grads("auto", q, k, v, g, causal=True)
    assert (count["attn.bwd.fused"], count["attn.bwd.two_pass"]) == (before[0], before[1] + 1)
    _grads("fused", q, k, v, g, causal=True)
    assert (count["attn.bwd.fused"], count["attn.bwd.two_pass"]) == (before[0] + 1, before[1] + 1)


# ---- the kernel alone, compiled for a described v5e --------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile_backward(one_chip, batch, heads, kv_heads, t, d, window, impl):
    """The backward rule alone, on operands as the forward rule leaves them
    (heads before positions): q, k, v, the output and its log-sum-exp column."""
    like = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    q, kv = like(batch, heads, t, d), like(batch, kv_heads, t, d)
    kept = (q, kv, kv, q, like(batch, heads, t, dtype=jnp.float32))
    block_q, block_k = pa._tiles(window, None, None)

    def backward(kept, g):
        return pa._flash_bwd_dispatch(d**-0.5, True, t, block_q, block_k, False, impl, window, kept, g)

    return jax.jit(backward).lower(kept, q).compile()


def _kernels(program):
    """The flash kernels a compiled program calls, by instruction name."""
    return sorted(set(re.findall(r"^\s*(?:ROOT )?%((?:flash|swa)_\w+?)(?:\.\d+)? = ", program.as_text(), re.M)))


@pytest.mark.parametrize("cell, batch", [("glm47flash", 2), ("trinity-full", 1), ("trinity-window", 1)])
def test_the_fused_backward_compiles_at_a_cells_shape_with_nothing_float32_beside_it(one_chip, cell, batch):
    heads, kv_heads, t, d, window = CELLS[cell]
    program = _compile_backward(one_chip, batch, heads, kv_heads, t, d, window, "auto")
    memory = program.memory_analysis()
    assert _kernels(program) == ["flash_bwd_fused" if window is None else "swa_bwd_fused"]
    # float32 beside the kernel: the two lane-broadcast columns it reads (log-sum-exp and D, 128 lanes a row)
    # and nothing of the operands' size: no float32 dQ to cast, no partial dk, dv a query head to sum
    columns = 2 * batch * heads * t * 128 * 4
    assert columns <= memory.temp_size_in_bytes < columns + 2 * MIB
    # dq a query head, dk and dv a key-value head each, in the operands' bfloat16
    assert 0 <= memory.output_size_in_bytes - (heads + 2 * kv_heads) * batch * t * d * 2 < MIB


def test_past_the_vmem_the_two_passes_compile_and_the_forced_kernel_is_refused(one_chip):
    heads, kv_heads, t, d, window = 2, 2, *CELLS["past-the-vmem"][2:]
    assert _kernels(_compile_backward(one_chip, 1, heads, kv_heads, t, d, window, "auto")) == ["flash_bwd_dkv", "flash_bwd_dq"]
    # Mosaic counts 137.18 MiB of the chip's 128 where this file's reckoning reads 141: the rule errs on the safe side
    with pytest.raises(Exception, match="vmem"):
        _compile_backward(one_chip, 1, heads, kv_heads, t, d, window, "fused")
