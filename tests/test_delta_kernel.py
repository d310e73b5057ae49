"""The gated delta rule's chunk step as a Pallas kernel
(``heat_tpu/nn/pallas_delta.py``), run in the Pallas interpreter on the CPU:
(a) the kernel form of the rule against the XLA form and against the
reference's recurrence, outputs and the gradients of all five inputs, at head
size 128 with one key head serving two value heads; (b) which form
``gated_delta_rule`` takes, and the counter that says so; (c) what the
differentiated kernel form holds between its passes. A CPU run gives results
and counts, no time.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from chipbench.references import qwen3_next_plain as ref
from heat_tpu import telemetry
from heat_tpu.nn import deltanet, gated_delta_rule, pallas_delta

CHUNK = 64
rel = ref.rel_gap


def inputs(t, b=1, hk=1, h=2, dk=128, dv=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = functools.partial(jax.random.normal, dtype=jnp.float32)
    q, k = normal(ks[0], (b, t, hk, dk)), normal(ks[1], (b, t, hk, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / 4
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = normal(ks[2], (b, t, h, dv))
    g = -0.5 * jax.nn.softplus(normal(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def kernel_form(dtype):
    step = functools.partial(pallas_delta.kernel_chunk_step, dtype=dtype, interpret=True)
    return lambda *a: deltanet._chunked_rule(step, *a, CHUNK)


def xla_form(dtype):
    step = functools.partial(deltanet._xla_chunk_step, dtype=dtype)
    return lambda *a: deltanet._chunked_rule(step, *a, CHUNK)


def recurrence(q, k, v, g, beta):
    repeat = lambda a: jnp.repeat(a, v.shape[2] // a.shape[2], axis=2)  # noqa: E731
    return ref.delta_rule(repeat(q), repeat(k), v, jnp.exp(g), beta)


def with_gradients(rule, args):
    loss = lambda *a: jnp.sum(jnp.sin(3 * rule(*a)))  # noqa: E731
    return rule(*args), jax.grad(loss, argnums=range(5))(*args)


# float32: the same sums in another order (2e-5 as tests/test_qwen3_next.py has it; the
# decay's gradient through a chunk's running sum 2e-3). bfloat16 operands against
# the float32 recurrence: 2^-9 an operand through a chunk's solve and products
# (4e-3 observed on the outputs); the two forms round the same operands and differ
# where a sum's order turns one rounding (1e-3)
LIMITS = {jnp.float32: (2e-5, 2e-3, 2e-5), jnp.bfloat16: (3e-2, 6e-2, 1e-3)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("t", [64, 128, 100])  # one chunk, two, two with the second padded
def test_the_kernel_form_is_the_xla_form_and_the_recurrence(t, dtype):
    """The rule with its chunk step run by the two kernels in the interpreter:
    outputs and the gradients of q, k, v, g and beta; the key head's gradients
    are the sums over the two value heads it serves."""
    to_recurrence, to_recurrence_decay, to_xla = LIMITS[dtype]
    args = inputs(t)
    with jax.default_matmul_precision("highest"):
        got, g_got = with_gradients(kernel_form(dtype), args)
        xla, g_xla = with_gradients(xla_form(dtype), args)
        want, g_want = with_gradients(recurrence, args)
    assert got.shape == want.shape == (1, t, 2, 128) and got.dtype == jnp.float32
    assert rel(got, xla) < to_xla and rel(got, want) < to_recurrence
    for name, a, x, w in zip(("q", "k", "v", "g", "beta"), g_got, g_xla, g_want):
        assert a.shape == w.shape, name
        assert rel(a, x) < to_xla, name
        assert rel(a, w) < (to_recurrence_decay if name == "g" else to_recurrence), name


def test_several_sequences_and_grid_steps_of_heads():
    """Two sequences, sixteen value heads on eight key heads: two grid steps of
    eight heads a sequence, each reading its four key heads."""
    args = inputs(CHUNK, b=2, hk=8, h=16)
    got, g_got = with_gradients(kernel_form(jnp.float32), args)
    want, g_want = with_gradients(xla_form(jnp.float32), args)
    assert rel(got, want) < 2e-5
    for a, w in zip(g_got, g_want):
        assert rel(a, w) < 2e-5


@pytest.fixture
def counters():
    counts = telemetry.get_registry().counters
    before = {name: counts.get(name, 0) for name in ("gdn.rule.kernel", "gdn.rule.xla")}
    return lambda: {name: counts.get(name, 0) - n for name, n in before.items()}


@pytest.mark.parametrize("what, sizes", [
    ("a head size that fills no lane", dict(dk=16, dv=8)),
    ("a chunk of no whole tile", dict(chunk=24)),
    ("heads that divide into no grid step", dict(hk=4, h=12)),
    ("no TPU", dict(backend="cpu")),
])
def test_an_ineligible_rule_takes_the_xla_form(monkeypatch, counters, what, sizes):
    sizes = {"dk": 128, "dv": 128, "hk": 1, "h": 2, "chunk": CHUNK, "backend": "tpu", **sizes}
    monkeypatch.setattr(jax, "default_backend", lambda: sizes["backend"])
    args = inputs(96, hk=sizes["hk"], h=sizes["h"], dk=sizes["dk"], dv=sizes["dv"])
    jaxpr = jax.make_jaxpr(lambda *a: gated_delta_rule(*a, chunk=sizes["chunk"]))(*args)
    assert counters() == {"gdn.rule.kernel": 0, "gdn.rule.xla": 1}, what
    assert "pallas_call" not in str(jaxpr), what


def test_an_eligible_rule_takes_the_kernel(monkeypatch, counters):
    """Head sizes of 128, the chunk of 64 and a TPU: the trace holds the
    kernel, once for the scan's body, and says so."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr = str(jax.make_jaxpr(lambda *a: gated_delta_rule(*a, dtype=jnp.bfloat16))(*inputs(256)))
    assert counters() == {"gdn.rule.kernel": 1, "gdn.rule.xla": 0}
    assert jaxpr.count("pallas_call") == 1 and "delta_chunk_fwd" in jaxpr


def test_the_differentiated_kernel_form_keeps_a_state_a_chunk_and_nothing_chunk_by_chunk(monkeypatch):
    """Lowered for the TPU (nothing compiled or run): two Mosaic calls, the
    forward scan stacks one state a chunk, ``(chunks, B, H, Dk, Dv)``, and no
    array anywhere is chunk x chunk or holds a state a position."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = inputs(4 * CHUNK)
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a, dtype=jnp.bfloat16)), argnums=range(5)))
    text = grad.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert "delta_chunk_fwd" in text and "delta_chunk_bwd" in text
    assert "tensor<4x1x2x128x128xf32>" in text  # 256 / 64 chunk states
    assert "x64x64xf32>" not in text  # decay, inside, solve, scores: in VMEM alone
    assert "tensor<256x1x2x128x128xf32>" not in text and "tensor<1x256x2x128x128xf32>" not in text
    assert "tensor<1x256x2x128xf32>" in text  # v and the output at their own shape, no repeated q or k
    assert "tensor<1x256x2x128x" not in text.replace("tensor<1x256x2x128xf32>", "")
