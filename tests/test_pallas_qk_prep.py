"""Queries and keys from their projections to the flash kernels as Pallas
kernels (``heat_tpu/nn/pallas_qk_prep.py``), run in the Pallas interpreter on
the CPU: (a) the kernels against the lines ``MultiHeadAttention`` runs where no
kernel does (the norm's own module, ``rotary``, the cast, the transpose into
the kernels' layout), results and both gradients, over the norms, rotary
fractions and head shapes of the published configurations; (b) which calls
take the kernels, and the counters that say which form a traced pass took;
(c) that a kernel's body is traced once a shape. A CPU run gives results and
counts, no time.
"""

import itertools

import jax
import jax.numpy as jnp
import pytest

from heat_tpu import telemetry
from heat_tpu.core.communication import MeshCommunication
from heat_tpu.nn import pallas_qk_prep, transformer

EPS, THETA = 1e-6, 10000.0
NORMS = ["rmsnorm", "rmsnorm_zero", "row", None]  # over a head in its two forms, over the row, none
FRACTIONS = [1.0, 0.25, None]
HEADS = [(32, 128), (4, 128), (16, 256), (2, 256)]  # Trinity-Mini's q and k, Qwen3-Next's
CASES = [
    pytest.param(norm, fraction, h, d, id=f"{norm}-{fraction}-{h}x{d}")
    for norm, fraction, (h, d) in itertools.product(NORMS, FRACTIONS, HEADS) if norm or fraction
]
T, ROWS = 40, 16  # two whole row steps and half of one


def both_forms(monkeypatch, model, params, tokens, loss, apply=None):
    """A model's parameter shapes, output and gradients with XLA's lines and
    with the kernels forced (a CPU takes XLA's lines by itself) and run in the
    interpreter, and how many query or key passes each form's traces counted:
    what the four published configurations' own test files hold equal."""
    apply = apply or (lambda p: model.apply(p, tokens))

    def program():
        before = dict(telemetry.get_registry().counters)
        tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        out, grads = apply(params), jax.grad(lambda p: loss(p, tokens)[0])(params)
        after = telemetry.get_registry().counters
        passes = {form: after.get(f"attn.qk_prep.{form}", 0) - before.get(f"attn.qk_prep.{form}", 0) for form in ("kernel", "xla")}
        return [(a.shape, a.dtype) for a in jax.tree.leaves(tree)], jax.tree.structure(tree), out, grads, passes

    xla = program()
    monkeypatch.setattr(pallas_qk_prep, "takes_kernel", lambda impl, *a: impl == "flash")
    kernel = program()
    assert kernel[:2] == xla[:2]  # the parameter tree: paths, shapes, types
    assert xla[4]["kernel"] == 0 and kernel[4]["xla"] == 0 and kernel[4]["kernel"] == xla[4]["xla"] > 0
    return xla[2:], kernel[2:]


def rel(a, b):
    a, b = (jnp.asarray(v, jnp.float32).ravel() for v in (a, b))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def xla_form(x, scale, norm, fraction, d, dtype):
    """``MultiHeadAttention``'s lines without the kernels, and the flash kernels' transpose."""
    q = x[..., :d]
    if norm == "row":
        over_heads = dict(reduction_axes=(-2, -1), feature_axes=(-2, -1))
        q = transformer._norm("rmsnorm", EPS, jnp.float32, "q_norm", **over_heads).apply({"params": {"scale": scale}}, q)
    elif norm:
        q = transformer._norm(norm, EPS, jnp.float32, "q_norm").apply({"params": {"scale": scale}}, q)
    if fraction:
        q = transformer.rotary(q, THETA, fraction)
    return q.astype(dtype).transpose(0, 2, 1, 3)


def kernel_form(x, scale, norm, fraction, d, dtype):
    gain = None if norm is None else (1.0 + scale if norm == "rmsnorm_zero" else scale).reshape(-1, d)
    over = None if norm is None else "row" if norm == "row" else "head"
    p = pallas_qk_prep.Pass(d, over, EPS, THETA if fraction else None, fraction or 1.0, dtype, ROWS, True)
    return pallas_qk_prep.qk_prep(x, gain, p)


def inputs(norm, h, d, gated, b=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (b, T, h, 2 * d if gated else d), jnp.float32)
    scale = None if norm is None else 0.3 * jax.random.normal(ks[1], (h, d) if norm == "row" else (d,), jnp.float32)
    if norm in ("rmsnorm", "row"):
        scale = 1.0 + scale
    return x, scale, jax.random.normal(ks[2], (b, h, T, d), jnp.float32)


@pytest.mark.parametrize("norm, fraction, h, d", CASES)
def test_the_forward_kernel_is_the_xla_lines(norm, fraction, h, d):
    """bfloat16 out of float32 arithmetic: the two forms round the same values,
    which differ in their last float32 place, so a few land on either side."""
    gated = h > 4  # the queries stand beside their gates, the keys alone
    x, scale, _ = inputs(norm, h, d, gated, b=2 if h == 4 else 1)
    got, want = (form(x, scale, norm, fraction, d, jnp.bfloat16) for form in (kernel_form, xla_form))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert rel(got, want) < 2e-3
    assert rel(kernel_form(x, scale, norm, fraction, d, jnp.float32), xla_form(x, scale, norm, fraction, d, jnp.float32)) < 1e-5


@pytest.mark.parametrize("norm, fraction, h, d", CASES)
def test_the_backward_kernel_gives_the_xla_lines_gradients(norm, fraction, h, d):
    gated = h > 4
    x, scale, cotangent = inputs(norm, h, d, gated, b=2 if h == 4 else 1)

    def gradients(form):
        loss = lambda x, scale: jnp.sum(form(x, scale, norm, fraction, d, jnp.float32) * cotangent)  # noqa: E731
        return jax.grad(loss, argnums=(0, 1) if norm else (0,))(x, scale)

    got, want = gradients(kernel_form), gradients(xla_form)
    assert got[0].shape == x.shape and got[0].dtype == x.dtype
    assert rel(got[0], want[0]) < 1e-5
    if gated:
        assert not jnp.any(got[0][..., d:])  # nothing flows into the gates' lanes from here
    if norm:
        assert got[1].shape == scale.shape and rel(got[1], want[1]) < 1e-5


ADMITTED = dict(attn_impl="flash", comm=None, d_head=128, norm="head", norm_kind="rmsnorm", rotary=True)


@pytest.mark.parametrize("refused, call, backend", [
    ("heads of 64", dict(d_head=64), "tpu"),
    ("a sharded batch", dict(comm="two devices"), "tpu"),
    ("another attention form", dict(attn_impl="local"), "tpu"),
    ("a layer norm over the head", dict(norm_kind="layernorm"), "tpu"),
    ("no norm and no rotary", dict(norm=None, rotary=False), "tpu"),
    ("another backend", {}, "cpu"),
])
def test_takes_kernel_refuses(monkeypatch, refused, call, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if "comm" in call:
        call = dict(call, comm=MeshCommunication(devices=jax.devices()[:2]))
    assert pallas_qk_prep.takes_kernel(**ADMITTED) == (backend == "tpu")
    assert not pallas_qk_prep.takes_kernel(**dict(ADMITTED, **call)), refused


@pytest.mark.parametrize("call", [
    dict(norm="head", rotary=False),  # Trinity-Mini's two full layers
    dict(norm="head", norm_kind="rmsnorm_zero", d_head=256),  # Qwen3-Next
    dict(norm=None),  # Ouro: rotary alone (whatever the blocks' own norm is)
    dict(norm="row"),  # OLMoE: the norm over all of hidden
    dict(comm="one device"),
], ids=["no-rotary", "rmsnorm_zero-256", "rotary-alone", "row-norm", "one-device"])
def test_takes_kernel_admits(monkeypatch, call):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if "comm" in call:
        call = dict(call, comm=MeshCommunication(devices=jax.devices()[:1]))
    assert pallas_qk_prep.takes_kernel(**dict(ADMITTED, **call))


def attention(**fields):
    from heat_tpu.nn import MultiHeadAttention

    return MultiHeadAttention(**{**dict(
        num_heads=4, attn_impl="flash", block_size=16, qk_norm_eps=1e-6, qk_norm_over="head", rope_theta=THETA,
        num_kv_heads=2, head_dim=16, gate=True,
    ), **fields})


@pytest.mark.parametrize("form, fields, counted", [
    ("kernel", {}, 2),
    ("xla", {"attn_impl": "local"}, 2),
    ("xla", {"qk_norm_eps": None, "rope_theta": None, "attn_impl": "local"}, 0),  # no pass to count
], ids=["kernel", "xla", "neither"])
def test_the_counters_count_one_a_traced_query_or_key_pass(monkeypatch, form, fields, counted):
    monkeypatch.setattr(pallas_qk_prep, "takes_kernel", lambda impl, *a: impl == "flash")
    layer = attention(**fields)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 32), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)
    before = dict(telemetry.get_registry().counters)
    layer.apply(params, x)
    after = telemetry.get_registry().counters
    grown = {f: after.get(f"attn.qk_prep.{f}", 0) - before.get(f"attn.qk_prep.{f}", 0) for f in ("kernel", "xla")}
    assert grown == {"kernel": 0, "xla": 0, form: counted}


def test_a_kernel_body_is_traced_once_a_shape(monkeypatch):
    """Three layers under ``remat``, differentiated: queries and keys are two
    shapes, so two traces of each body whatever the layers and passes (a bare
    ``pallas_call`` is traced at every call site: twelve forward, six backward)."""
    from heat_tpu.nn import TransformerLM

    calls = {"_fwd_kernel": 0, "_bwd_kernel": 0}

    def counting(name):
        body = getattr(pallas_qk_prep, name)

        def counted(*args, **kwargs):
            calls[name] += 1
            return body(*args, **kwargs)
        return counted

    for name in calls:
        monkeypatch.setattr(pallas_qk_prep, name, counting(name))
    monkeypatch.setattr(pallas_qk_prep, "takes_kernel", lambda impl, *a: impl == "flash")
    model = TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_kv_heads=2, num_layers=3, max_len=64, remat=True, attn_impl="flash",
        block_size=16, norm="rmsnorm", positions="rope", qk_norm="head",
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])
    for jitted in (pallas_qk_prep._forward, pallas_qk_prep._backward):
        jitted.clear_cache()  # the initialisation was another program, at other shapes
    calls.update(dict.fromkeys(calls, 0))
    jax.make_jaxpr(jax.grad(lambda p: jnp.mean(model.apply(p, tokens).astype(jnp.float32) ** 2)))(params)
    assert calls == {"_fwd_kernel": 2, "_bwd_kernel": 2}
    for jitted in (pallas_qk_prep._forward, pallas_qk_prep._backward):
        jitted.clear_cache()  # the counting bodies are in no later test's


@pytest.mark.parametrize("counted, share", [
    ({"kernel": 48, "xla": 0}, 1.0), ({"xla": 24}, 0.0), ({"kernel": 6, "xla": 18}, 0.25), ({}, None),
], ids=["kernel", "xla", "mixed", "a-parent-without-the-counters"])
def test_the_benchmarks_metric_reads_the_share_and_nothing_from_a_program_without_the_counters(monkeypatch, counted, share):
    import importlib.util
    import pathlib
    import types

    path = pathlib.Path(__file__).parent.parent / "chipbench" / "metrics" / "qk_prep_kernel_share.py"
    spec = importlib.util.spec_from_file_location("qk_prep_kernel_share", path)
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)
    registry = types.SimpleNamespace(counters={f"attn.qk_prep.{form}": n for form, n in counted.items()})
    monkeypatch.setattr(telemetry, "get_registry", lambda: registry)
    reading = types.SimpleNamespace(notes={})
    assert metric.read(reading) == share
    assert reading.notes == ({} if share is None else {"qk_prep_passes": {"kernel": 0, "xla": 0, **counted}})
