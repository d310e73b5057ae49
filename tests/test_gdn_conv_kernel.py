"""The Gated DeltaNet mixer's pass before the rule as Pallas kernels
(``heat_tpu/nn/pallas_gdn_conv.py``), run in the Pallas interpreter on the CPU:
(a) the kernels against ``conv_silu`` + ``l2_normalise``, XLA's form, results
and both gradients; (b) which shapes take the kernel, and the counter that
says which form a trace took; (c) that a mixer kernel is traced once a
program: a three-mixer model slice with ``remat=True`` and two sequences,
differentiated, calls each kernel's body once, where a bare ``pallas_call``
is traced at every call site. A CPU run gives results and counts, no time.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from heat_tpu import telemetry
from heat_tpu.nn import TransformerLM, deltanet, pallas_delta, pallas_gdn_conv


def rel(a, b):
    a, b = (jnp.asarray(v, jnp.float32).ravel() for v in (a, b))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def xla_form(x, w, hk, dk):
    """The pass as ``GatedDeltaNet`` runs it where no kernel does, q, k, v by channel."""
    b, t, _ = x.shape
    qkv = deltanet.conv_silu(x, w)
    q = deltanet.l2_normalise(qkv[..., :hk * dk].reshape(b, t, hk, dk)) * dk ** -0.5
    k = deltanet.l2_normalise(qkv[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk))
    return q.reshape(b, t, -1), k.reshape(b, t, -1), qkv[..., 2 * hk * dk:]


def inputs(b, t, hk, hv, dk, dtype, taps=4, seed=0):
    channels = 2 * hk * dk + hv * dk
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, t, channels), jnp.float32).astype(dtype)
    w = jax.random.uniform(ks[1], (channels, taps), jnp.float32, -0.5, 0.5)
    cotangents = [jax.random.normal(k, (b, t, n), jnp.float32) for k, n in zip(ks[2:], (hk * dk, hk * dk, hv * dk))]
    return x, w, cotangents


def with_gradients(form, x, w, cotangents):
    loss = lambda x, w: sum(jnp.sum(o * c) for o, c in zip(form(x, w), cotangents))  # noqa: E731
    return form(x, w), jax.grad(loss, argnums=(0, 1))(x, w)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("hk, hv, t, rows", [
    (1, 2, 48, 16),  # two value heads a key head, three row steps
    (2, 2, 32, 256),  # one value head a key head, the whole length one step
    (1, 4, 80, 32),  # a length that is no multiple of the row step: the last one hangs over the end
], ids=["ratio2", "ratio1", "ragged"])
def test_the_kernels_are_the_xla_form_and_its_gradients(hk, hv, t, rows, b, dtype):
    """float32 to 1e-5 whatever ``x`` is stored as: both forms widen it first.
    The cotangent of ``x`` leaves in ``x``'s type, the taps' in float32."""
    x, w, cotangents = inputs(b, t, hk, hv, 128, dtype)
    kernel = lambda x, w: pallas_gdn_conv.conv_silu_norm(x, w, hk, 128, rows, True)  # noqa: E731
    got, (g_x, g_w) = with_gradients(kernel, x, w, cotangents)
    want, (w_x, w_w) = with_gradients(functools.partial(xla_form, hk=hk, dk=128), x, w, cotangents)
    for name, a, e in zip("qkv", got, want):
        assert a.shape == e.shape and a.dtype == jnp.float32, name
        assert rel(a, e) < 1e-5, name
    assert g_x.shape == x.shape and g_x.dtype == x.dtype and g_w.shape == w.shape and g_w.dtype == jnp.float32
    # a bfloat16 cotangent is the float32 one rounded once: a rounding that falls the other way is 2^-8 of an entry
    assert rel(g_x, w_x) < (1e-5 if dtype == jnp.float32 else 2e-3)
    assert rel(g_w, w_w) < 1e-5


def test_other_taps_and_a_wider_head():
    """Two taps, heads of 256: the lanes a step are whole heads."""
    x, w, cotangents = inputs(1, 24, 1, 1, 256, jnp.float32, taps=2)
    kernel = lambda x, w: pallas_gdn_conv.conv_silu_norm(x, w, 1, 256, 8, True)  # noqa: E731
    got, g_got = with_gradients(kernel, x, w, cotangents)
    want, g_want = with_gradients(functools.partial(xla_form, hk=1, dk=256), x, w, cotangents)
    assert all(rel(a, e) < 1e-5 for a, e in zip(got + g_got, want + g_want))


@pytest.mark.parametrize("key_dim, value_dim, dk, lanes", [
    (2048, 4096, 128, 512),  # the Qwen3-Next cell: four heads a step, 4 + 4 + 8 channel steps
    (128, 256, 128, 128), (256, 256, 128, 256), (384, 768, 128, 384), (256, 256, 256, 256), (4096, 4096, 2048, 2048),
])
def test_a_grid_step_holds_whole_heads_that_divide_every_part(key_dim, value_dim, dk, lanes):
    assert pallas_gdn_conv._lanes(key_dim, value_dim, dk) == lanes


@pytest.fixture
def counters():
    counts = telemetry.get_registry().counters
    before = {name: counts.get(name, 0) for name in ("gdn.conv.kernel", "gdn.conv.xla")}
    return lambda: {name: counts.get(name, 0) - n for name, n in before.items()}


@pytest.mark.parametrize("what, sizes, takes", [
    ("the cell's shapes", {}, True),
    ("bfloat16 at a length of whole tiles of 16", dict(dtype=jnp.bfloat16, t=48), True),
    ("one value head a key head", dict(hv=1), True),
    ("two taps", dict(taps=2), True),
    ("a head size that fills no lane", dict(dk=64), False),
    ("a value head that fills no lane", dict(dv=64), False),
    ("a length of no whole sublane tile", dict(t=44), False),
    ("bfloat16 at a length of no whole tile of 16", dict(dtype=jnp.bfloat16, t=40), False),
    ("taps that reach past one tile", dict(taps=10), False),
    ("a byte a channel", dict(dtype=jnp.int8), False),
    ("no TPU", dict(backend="cpu"), False),
])
def test_the_shape_and_the_backend_decide(monkeypatch, counters, what, sizes, takes):
    s = {"dtype": jnp.float32, "t": 40, "hk": 1, "hv": 2, "dk": 128, "dv": 128, "taps": 4, "backend": "tpu", **sizes}
    monkeypatch.setattr(jax, "default_backend", lambda: s["backend"])
    channels = 2 * s["hk"] * s["dk"] + s["hv"] * s["dv"]
    x, w = jnp.zeros((1, s["t"], channels), s["dtype"]), jnp.zeros((channels, s["taps"]), jnp.float32)
    assert pallas_gdn_conv.takes_kernel(x.shape, x.dtype, w.shape, s["hk"], s["dk"], s["hv"], s["dv"]) is takes, what
    if s["dtype"] == jnp.int8:
        return
    jaxpr = str(jax.make_jaxpr(lambda x, w: deltanet.conv_silu_norm(x, w, s["hk"], s["dk"], s["hv"], s["dv"]))(x, w))
    assert counters() == {"gdn.conv.kernel": int(takes), "gdn.conv.xla": int(not takes)}, what
    assert ("gdn_conv_fwd" in jaxpr) is takes, what


def test_either_form_gives_the_mixer_its_heads(monkeypatch):
    """``deltanet.conv_silu_norm`` hands ``q, k (B, T, Hk, Dk)`` and ``v (B, T,
    Hv, Dv)`` to the rule, from the kernel (here in the interpreter) as from XLA."""
    x, w, _ = inputs(2, 16, 1, 2, 128, jnp.float32)
    want = deltanet.conv_silu_norm(x, w, 1, 128, 2, 128)
    monkeypatch.setattr(pallas_gdn_conv, "takes_kernel", lambda *a: True)
    conv = pallas_gdn_conv.conv_silu_norm
    monkeypatch.setattr(pallas_gdn_conv, "conv_silu_norm", lambda x, w, hk, dk, rows, _: conv(x, w, hk, dk, rows, True))
    got = deltanet.conv_silu_norm(x, w, 1, 128, 2, 128)
    assert [a.shape for a in got] == [(2, 16, 1, 128), (2, 16, 1, 128), (2, 16, 2, 128)] == [a.shape for a in want]
    assert all(rel(a, e) < 1e-5 for a, e in zip(got, want))


# -- once a program ----------------------------------------------------------------------


BODIES = {
    "gdn_conv_fwd": (pallas_gdn_conv, "_fwd_kernel"), "gdn_conv_bwd": (pallas_gdn_conv, "_bwd_kernel"),
    "delta_chunk_fwd": (pallas_delta, "_fwd_kernel"), "delta_chunk_bwd": (pallas_delta, "_bwd_kernel"),
}


def forget_traces():
    """The four jitted kernel calls' own caches, and nothing else of the process."""
    for jitted in (pallas_gdn_conv._forward, pallas_gdn_conv._backward, pallas_delta._step_forward, pallas_delta._step_backward):
        jitted.clear_cache()


@pytest.fixture
def interpreted_mixers(monkeypatch):
    """Both predicates say yes and both kernel forms run in the interpreter;
    every Python call of a kernel's body (one a trace of it) is counted."""
    calls = dict.fromkeys(BODIES, 0)

    def counting(name, body):
        @functools.wraps(body)
        def counted(*args, **kwargs):
            calls[name] += 1
            return body(*args, **kwargs)
        return counted

    for name, (module, attr) in BODIES.items():
        monkeypatch.setattr(module, attr, counting(name, getattr(module, attr)))
    monkeypatch.setattr(deltanet, "takes_kernel", lambda *a: True)
    monkeypatch.setattr(pallas_gdn_conv, "takes_kernel", lambda *a: True)
    step = pallas_delta.kernel_chunk_step
    monkeypatch.setattr(deltanet, "kernel_chunk_step", lambda *a, dtype, interpret: step(*a, dtype, True))
    conv = pallas_gdn_conv.conv_silu_norm
    monkeypatch.setattr(pallas_gdn_conv, "conv_silu_norm", lambda x, w, hk, dk, rows, _: conv(x, w, hk, dk, rows, True))
    forget_traces()  # a body traced by an earlier test would be found in jit's cache and not counted
    yield calls
    forget_traces()  # and the counting bodies are in no later test's


def three_mixers():
    model = TransformerLM(
        vocab_size=64, d_model=32, num_heads=2, num_layers=3, max_len=64, mixers=("deltanet",), remat=True,
        gdn_key_heads=1, gdn_value_heads=2, gdn_key_dim=128, gdn_value_dim=128,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])
    return model, params, tokens


def call_sites(jaxpr, name):
    """Equations of ``jaxpr``, at any depth, that call the jitted function ``name``."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name in ("jit", "pjit") and eqn.params["name"] == name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += call_sites(inner, name)
    return n


def test_a_mixer_kernel_is_traced_once_a_program(interpreted_mixers):
    """Three mixers, each run forward in the step, in its block's
    rematerialisation and in its sequence's checkpoint, and backward once:
    nine forward and three backward call sites of each pair of kernels at the
    least, and one trace of each body, where a bare ``pallas_call`` traces it
    at every site."""
    model, params, tokens = three_mixers()
    forget_traces()  # the initialisation was another program (a chunk step's trace there would be this one's too)
    interpreted_mixers.update(dict.fromkeys(BODIES, 0))
    loss = lambda p: jnp.mean(model.apply(p, tokens).astype(jnp.float32) ** 2)  # noqa: E731
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    assert interpreted_mixers == dict.fromkeys(BODIES, 1)
    # the sites are all there (the rule's forward also where a scan's transpose holds its forward again)
    for name, least in {"_forward": 9, "_backward": 3, "_step_forward": 9, "_step_backward": 3}.items():
        assert call_sites(jaxpr, name) >= least, name
