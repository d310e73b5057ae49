"""OLMoE through ``ht.nn`` against the plain reference
(``chipbench/references/olmoe_plain.py``: float32 ``jax.numpy``, experts as a
loop, its own AdamW, nothing of heat_tpu) at a size a CPU test can hold:
hidden 64, 4 heads, 8 experts top-2, expert width 32, vocabulary 257, T 32,
seeded weights. The same comparison runs on the chip at the published widths
inside the benchmark's ``correct`` (``chipbench/kinds/lm_step.py``).

Every tolerance has its reason beside it. The float32 ones are a few float32
roundings of sums of a few hundred terms; the mixed-precision ones lie between
what bfloat16 *operands* cost (the guarantee) and what a bfloat16 router,
norms and results cost (the control, which must fail).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import heat_tpu as ht
from chipbench.kinds import lm_step
from chipbench.references import olmoe_plain as ref
from heat_tpu.nn import (
    DataParallel, DroplessMoE, TransformerLM, causal_lm_loss, olmoe_1b_7b, read_routing,
)
from heat_tpu.nn import functional as F
from heat_tpu.nn.functional import blocked_cross_entropy
from heat_tpu.nn.moe import rows_computed

C = dict(
    hidden_size=64, num_attention_heads=4, num_experts=8, num_experts_per_tok=2,
    intermediate_size=32, vocab_size=257, num_hidden_layers=1, rms_norm_eps=1e-5,
    rope_theta=10000.0,
)
COEF = {"load_balance": 0.01, "router_z": 0.001}
OPT = {"lr": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip": 1.0,
       "warmup_steps": 4, "coef": COEF}
SEED, T = 11, 32

# float32 against float32 at "highest": sums of up to 257 products, each rounded
# once (6e-8), through a dozen layers of arithmetic; observed 1e-7..3e-6
F32 = 2e-5


def tiny(layers=1, **fields):
    arch = dict(
        vocab_size=257, d_model=64, num_heads=4, num_layers=layers, max_len=T,
        norm="rmsnorm", norm_eps=1e-5, positions="rope", qk_norm=True, ffn="moe",
        d_ff=32, num_experts=8, experts_per_token=2, attn_impl="flash",
    )
    return TransformerLM(**{**arch, **fields})


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(SEED, C)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(ref.batch(SEED, 0, 2, T, ref.zipf_cdf(257)))


def highest(fn):
    @functools.wraps(fn)
    def run(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)

    return run


def rel(got, want):
    return ref.rel_gap(got, want)


# -- float32: the equations ---------------------------------------------------------


@highest
def test_forward_logits_match_the_reference(weights, tokens):
    got = tiny().apply(lm_step.to_system(weights, 4), tokens)
    want, _ = ref.logits_of(weights, tokens, C)
    assert got.shape == (2, T, 257)
    assert rel(got, want) < F32


@highest
def test_loss_with_both_auxiliary_terms_and_the_chosen_experts(weights, tokens):
    model = tiny()
    loss, aux = causal_lm_loss(model, load_balance_coef=0.01, router_z_coef=0.001)(
        lm_step.to_system(weights, 4), tokens
    )
    want, parts = ref.loss_parts(weights, tokens, C, COEF)
    for name in ("ce", "load_balance", "router_z"):
        assert rel(aux[name], parts[name]) < F32, name
    assert rel(loss, want) < F32
    assert float(loss) == pytest.approx(
        float(aux["ce"]) + 0.01 * float(aux["load_balance"]) + 0.001 * float(aux["router_z"]), rel=1e-6
    )
    # the auxiliary terms are not the cross-entropy in disguise
    assert float(aux["load_balance"]) >= 1.0 and float(aux["router_z"]) > 0.0
    # routing, exactly: the same experts for every token, the same counts, none dropped
    np.testing.assert_array_equal(aux["expert_counts"], parts["expert_counts"])
    assert int(aux["expert_counts"].sum()) == int(aux["assignments_due"]) == 2 * T * 2
    assert int(aux["assignments_computed"]) == 2 * T * 2
    _, sown = model.apply(lm_step.to_system(weights, 4), tokens, mutable=["aux"])
    chosen = sown["aux"]["block0"]["moe"]["moe"][0]["chosen"]
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(parts["chosen"][0], -1))


@highest
def test_gradients_of_every_parameter_group(weights, tokens):
    loss_fn = causal_lm_loss(tiny(), load_balance_coef=0.01, router_z_coef=0.001)
    grads = jax.grad(lambda p: loss_fn(p, tokens)[0])(lm_step.to_system(weights, 4))
    want = jax.grad(lambda p: ref.loss_parts(p, tokens, C, COEF)[0])(weights)
    got = lm_step.from_system(grads)
    seen = set()
    for (path, g), w in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(want)):
        name = path[-1].key
        seen.add(ref.group_of(name))
        assert float(jnp.abs(w).max()) > 0, name  # every parameter takes part
        assert rel(g, w) < 5 * F32, jax.tree_util.keystr(path)
    assert seen == set(ref.GROUPS)


@highest
def test_three_adamw_steps_through_make_train_step(weights):
    """The step a Heat user builds (DataParallel, optax's AdamW behind the
    clip) against the reference's own AdamW: losses and parameters."""
    comm = ht.core.communication.MeshCommunication(devices=jax.devices()[:1])
    model = tiny()
    opt = lm_step.optimizer(OPT)  # the kind's: optax's AdamW, the clip, the warm-up
    loss_fn = causal_lm_loss(model, load_balance_coef=0.01, router_z_coef=0.001)
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        loss_fn, has_aux=True
    )
    params = jax.tree.map(jnp.copy, lm_step.to_system(weights, 4))
    state = opt.init(params)
    rp = jax.tree.map(jnp.copy, weights)
    rs = ref.adamw_init(rp)
    cdf = ref.zipf_cdf(257)
    for i in range(3):
        batch = ref.batch(SEED, i, 2, T, cdf)  # on the host: the step places it
        params, state, loss, aux = step(params, state, batch)
        assert isinstance(loss, jax.Array) and isinstance(aux["expert_counts"], jax.Array)
        loss, aux = read_routing(loss, aux)
        rp, rs, want, parts = ref.train_step(rp, rs, jnp.asarray(batch), C, OPT)
        assert isinstance(loss, np.ndarray) and rel(loss, want) < F32, i
        np.testing.assert_array_equal(aux["expert_counts"], parts["expert_counts"])
    # after three steps at lr 1e-4, 2e-4, 3e-4 (warm-up over 4) every weight has
    # moved by about 6e-4: a wrong moment, decay, clip or warm-up shows as a gap
    # of that size
    for (path, g), w in zip(jax.tree.leaves_with_path(lm_step.from_system(params)), jax.tree.leaves(rp)):
        assert float(jnp.abs(g - w).max()) < 2e-6, jax.tree_util.keystr(path)
    moved = float(jnp.abs(rp["layers"][0]["wg"] - weights["layers"][0]["wg"]).max())
    assert 4e-4 < moved < 8e-4


def test_the_step_donates_its_state_and_counts_its_routing(weights):
    from heat_tpu import telemetry

    comm = ht.core.communication.MeshCommunication(devices=jax.devices()[:1])
    model = tiny()
    opt = optax.adamw(1e-3)
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        causal_lm_loss(model), has_aux=True
    )
    params = jax.device_put(jax.tree.map(jnp.copy, lm_step.to_system(weights, 4)), comm.replicated())
    state = opt.init(params)
    before = dict(telemetry.get_registry().counters)
    leaf = params["params"]["lm_head"]["kernel"]
    new_params, new_state, loss, aux = step(params, state, ref.batch(SEED, 0, 2, T, ref.zipf_cdf(257)))
    assert leaf.is_deleted() and all(l.is_deleted() for l in jax.tree.leaves(state) if l.ndim)
    # the step itself reads nothing back and counts nothing: the loop's one read does
    assert dict(telemetry.get_registry().counters).get("moe.steps", 0) == before.get("moe.steps", 0)
    read_routing(loss, aux)
    after = telemetry.get_registry().counters
    assert after["moe.assignments"] - before.get("moe.assignments", 0) == 2 * T * 2
    assert after["moe.dropped"] - before.get("moe.dropped", 0) == 0
    assert after["moe.steps"] - before.get("moe.steps", 0) == 1
    assert after["moe.load_max_over_mean"] - before.get("moe.load_max_over_mean", 0) >= 1.0
    # the compiled step aliases the state it is given
    lowered = step.lower(new_params, new_state, jnp.zeros((2, T), jnp.int32))
    nbytes = sum(l.nbytes for l in jax.tree.leaves((new_params, new_state)))
    assert lowered.compile().memory_analysis().alias_size_in_bytes >= 0.99 * nbytes


def test_the_step_and_the_read_of_its_loss_record_their_spans(weights):
    from heat_tpu import telemetry

    comm = ht.core.communication.MeshCommunication(devices=jax.devices()[:1])
    model = tiny()
    opt = optax.sgd(1e-2)
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        causal_lm_loss(model), has_aux=True
    )
    params = jax.tree.map(jnp.copy, lm_step.to_system(weights, 4))
    telemetry.enable()
    try:
        telemetry.spans(clear=True)
        *_, loss, aux = step(params, opt.init(params), ref.batch(SEED, 0, 2, T, ref.zipf_cdf(257)))
        read_routing(loss, aux)
        names = [s["name"] for s in telemetry.spans() if s.get("kind") == "span"]
    finally:
        telemetry.disable()
    root = "heat_tpu.train.step"
    assert [n for n in names if n.startswith(root)] == [
        root + ".prepare", root + ".launch", root, root + ".readback",
    ]


# -- the dropless layer by itself -----------------------------------------------------


def dense_loop(params, x, k):
    """The expert layer as a loop over every expert on every token."""
    p = params["params"]
    n = x.shape[0] * x.shape[1]
    xt = x.reshape(n, -1)
    probs = jax.nn.softmax(xt @ p["router"], axis=-1)
    w, e = jax.lax.top_k(probs, k)
    out = jnp.zeros_like(xt)
    for j in range(p["router"].shape[1]):
        weight = jnp.sum(jnp.where(e == j, w, 0.0), axis=-1)
        y = (jax.nn.silu(xt @ p["w_gate"][j]) * (xt @ p["w_up"][j])) @ p["w_down"][j]
        out = out + weight[:, None] * y
    return out.reshape(x.shape), e


@highest
@pytest.mark.parametrize("routing", ["seeded", "one_expert_takes_every_token"])
def test_dropless_layer_against_a_dense_loop(routing):
    layer = DroplessMoE(n_experts=8, top_k=2, d_ff=32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64), jnp.float32)
    params = {"params": layer.init(jax.random.PRNGKey(4), x)["params"]}
    if routing == "one_expert_takes_every_token":
        # expert 5 always first, expert 2 never chosen: a group of all 48
        # tokens, a group of none, and no capacity to overflow
        router = params["params"]["router"] * 0.01
        params["params"]["router"] = router.at[0].set(
            jnp.array([0, 0, -50.0, 0, 0, 50.0, 0, 0], jnp.float32)
        )
        x = x.at[..., 0].set(1.0)
    got, sown = layer.apply(params, x, mutable=["aux"])
    want, chosen = dense_loop(params, x, 2)
    aux = sown["aux"]["moe"][0]
    assert rel(got, want) < F32
    np.testing.assert_array_equal(np.sort(aux["chosen"], -1), np.sort(chosen, -1))
    assert int(aux["expert_counts"].sum()) == 2 * 48
    if routing == "one_expert_takes_every_token":
        assert int(aux["expert_counts"][5]) == 48 and int(aux["expert_counts"][2]) == 0
    # gradients through the sort, the grouped products and the un-sort
    f = lambda fn: jax.grad(lambda p, x: jnp.sum(fn(p, x) ** 2), argnums=(0, 1))(params, x)  # noqa: E731
    g_got = f(lambda p, x: layer.apply(p, x, mutable=["aux"])[0])
    g_want = f(lambda p, x: dense_loop(p, x, 2)[0])
    for (path, a), b in zip(jax.tree.leaves_with_path(g_got), jax.tree.leaves(g_want)):
        assert rel(a, b) < 5 * F32, jax.tree_util.keystr(path)


def test_rows_computed_counts_what_the_grouped_products_give_the_chosen_expert():
    """The dropped count is read from what the grouped products are given,
    the sorted experts and the group sizes, not from the routing's own sum:
    sizes cut by a capacity leave rows to the next expert or to none."""
    by_expert = jnp.array([0, 0, 0, 1, 2, 2, 2, 2], jnp.int32)
    assert int(rows_computed(by_expert, jnp.array([3, 1, 4], jnp.int32))) == 8
    # a capacity of 2: expert 0's third row falls to expert 1's group, expert 1's
    # row to expert 2's, and the last three rows lie in no group
    assert int(rows_computed(by_expert, jnp.array([2, 1, 2], jnp.int32))) == 3
    # an expert without rows is a group of none
    assert int(rows_computed(jnp.array([0, 2, 2], jnp.int32), jnp.array([1, 0, 2], jnp.int32))) == 3
    # rows that are not in the order of the groups
    assert int(rows_computed(jnp.array([1, 0, 1], jnp.int32), jnp.array([1, 2], jnp.int32))) == 1


# -- the blocked cross-entropy ---------------------------------------------------------


def _ce_case(n, seed=None):
    """Hidden states, a kernel, targets, and weights with zeros among them."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(n if seed is None else seed), 4)
    h = jax.random.normal(k1, (n, 48), jnp.float32)
    w = jax.random.normal(k2, (48, 257), jnp.float32) * 0.2
    y = jax.random.randint(k3, (n,), 0, 257)
    wt = jax.random.uniform(k4, (n,), jnp.float32).at[::5].set(0.0)
    return h, w, y, wt


def _plain_ce(h, w, y):
    return optax.softmax_cross_entropy_with_integer_labels(h @ w, y)


def loops(text):
    """The ``while`` instructions of a compiled program, each as its line."""
    return [line for line in text.splitlines() if " while(" in line]


def products_over(text, size):
    """The products of a compiled program (XLA:TPU writes a ``dot`` as a
    ``convolution``) with ``size`` among the dimensions of their result or of
    an operand, each as the list of those shapes. Operands are printed by
    name, so their shapes are looked up where they are defined."""
    shape = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = (\w+\[[\d,]*\])", text, re.M))
    found = []
    for result, operands in re.findall(r"= (\w+\[[\d,]*\])\S* (?:dot|convolution)\(([^)]*)\)", text):
        shapes = [result] + [shape.get(name.strip(), "") for name in operands.split(",")]
        if any(str(size) in re.findall(r"\d+", s) for s in shapes):
            found.append(shapes)
    return found


@highest
@pytest.mark.parametrize("n,block", [(64, 16), (50, 16), (10, 2048)])
def test_blocked_cross_entropy_against_the_plain_one(monkeypatch, n, block):
    monkeypatch.setattr(F, "CE_BLOCK", block)
    h, w, y, _ = _ce_case(n)
    got = blocked_cross_entropy(h, w, y)
    assert got.shape == () and got.dtype == jnp.float32
    # the same sums in another order: a few float32 roundings of n values near 6
    np.testing.assert_allclose(got, _plain_ce(h, w, y).sum(), rtol=2e-6)
    g_got = jax.grad(lambda h, w: blocked_cross_entropy(h, w, y), (0, 1))(h, w)
    g_want = jax.grad(lambda h, w: _plain_ce(h, w, y).sum(), (0, 1))(h, w)
    for a, b in zip(g_got, g_want):
        assert rel(a, b) < F32


@highest
@pytest.mark.parametrize("how", ["cotangent_3", "value_and_grad_has_aux", "undifferentiated"])
def test_weighted_blocked_cross_entropy_against_the_plain_one(monkeypatch, how):
    """Random weights with zeros among them and a ragged last block (50
    positions in blocks of 16): the value whether or not the call is
    differentiated, and all three gradients under a cotangent that is not 1
    and as ``value_and_grad(..., has_aux=True)`` takes them."""
    monkeypatch.setattr(F, "CE_BLOCK", 16)
    h, w, y, wt = _ce_case(50)
    scale = 3.0 if how == "cotangent_3" else 1.0

    def loss(ce):
        def f(h, w, wt):
            value = scale * ce(h, w, wt)
            return value, {"ce": value}

        return f

    got = loss(lambda h, w, wt: blocked_cross_entropy(h, w, y, wt))
    want = loss(lambda h, w, wt: jnp.sum(_plain_ce(h, w, y) * wt))
    if how == "undifferentiated":
        np.testing.assert_allclose(got(h, w, wt)[0], want(h, w, wt)[0], rtol=2e-6)
        return
    (v_got, aux), g_got = jax.value_and_grad(got, (0, 1, 2), has_aux=True)(h, w, wt)
    (v_want, _), g_want = jax.value_and_grad(want, (0, 1, 2), has_aux=True)(h, w, wt)
    np.testing.assert_allclose(v_got, v_want, rtol=2e-6)
    assert aux["ce"] == v_got
    for a, b in zip(g_got, g_want):
        assert a.dtype == b.dtype and rel(a, b) < F32


@highest
def test_the_cotangent_of_the_weights_is_the_cross_entropy_a_position(monkeypatch):
    """Exact, not a silent zero: the loss is linear in its weights. And no
    weights are weights of one."""
    monkeypatch.setattr(F, "CE_BLOCK", 16)
    h, w, y, wt = _ce_case(50)
    got = jax.grad(lambda wt: blocked_cross_entropy(h, w, y, wt))(wt)
    np.testing.assert_allclose(got, _plain_ce(h, w, y), rtol=0, atol=5e-6)
    assert blocked_cross_entropy(h, w, y) == blocked_cross_entropy(h, w, y, jnp.ones(50))


def test_blocked_cross_entropy_sums_its_gradients_in_float32_whatever_the_operands(monkeypatch):
    """bfloat16 operands, sixteen blocks through the one fused loop: the
    kernel's gradient is the sum over the blocks in a float32 carry and the
    hidden states' gradient comes back in float32, so one block of 256
    positions gives the same numbers to a float32 rounding. (A bfloat16 carry
    over sixteen blocks is off by 1e-3.)"""
    h, w, y, wt = _ce_case(256, seed=9)

    def grads(block):
        monkeypatch.setattr(F, "CE_BLOCK", block)
        return jax.grad(lambda h, w: blocked_cross_entropy(h, w, y, wt, dtype=jnp.bfloat16), (0, 1))(h, w)

    (dh, dw), (dh1, dw1) = grads(16), grads(256)
    assert dh.dtype == dw.dtype == jnp.float32
    assert rel(dw, dw1) < 1e-5 and rel(dh, dh1) < 1e-5


def test_blocked_cross_entropy_holds_no_full_logits(monkeypatch):
    """No array of positions x vocabulary in the program, forward or backward."""
    monkeypatch.setattr(F, "CE_BLOCK", 32)
    n, v = 256, 257
    h, w = jnp.zeros((n, 48)), jnp.zeros((48, v))
    y = jnp.zeros((n,), jnp.int32)
    text = jax.jit(jax.grad(lambda h, w: blocked_cross_entropy(h, w, y), (0, 1))).lower(
        h, w
    ).compile().as_text()
    assert f"[{n},{v}]" not in text and f"[8,32,{v}]" not in text
    assert f"[32,{v}]" in text


@pytest.mark.parametrize("differentiated", [True, False])
def test_the_gradients_are_formed_in_the_one_loop_and_only_under_differentiation(monkeypatch, differentiated):
    """From the compiled text. Differentiated: one ``while``, whose carry
    holds the kernel's float32 gradient, and three products with the
    vocabulary in them (logits, the hidden states' gradient, the kernel's):
    the logits are not formed twice. Not differentiated: one loop, the logits
    alone, no ``(D, V)`` float32 carry."""
    monkeypatch.setattr(F, "CE_BLOCK", 32)
    n, d, v = 256, 48, 257
    h, w = jnp.zeros((n, d), jnp.bfloat16), jnp.zeros((d, v), jnp.bfloat16)
    y, wt = jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.float32)
    fn = lambda h, w: blocked_cross_entropy(h, w, y, wt)  # noqa: E731
    if differentiated:
        fn = jax.value_and_grad(fn, (0, 1))
    text = jax.jit(fn).lower(h, w).compile().as_text()
    assert len(loops(text)) == 1, loops(text)
    assert len(products_over(text, v)) == (3 if differentiated else 1), products_over(text, v)
    assert (f"f32[{d},{v}]" in loops(text)[0]) == differentiated


@highest
def test_causal_lm_loss_is_the_plain_mean_over_the_models_logits(monkeypatch, weights, tokens):
    """The tiny model's cross-entropy and gradients through the blocked head
    (64 positions in blocks of 24, the last one ragged) against ``optax`` over
    the logits of ``model.apply``: the mean over the T - 1 targets a row, the
    last position of each row left out."""
    monkeypatch.setattr(F, "CE_BLOCK", 24)
    model, params = tiny(), lm_step.to_system(weights, 4)

    def plain(params):
        logits = model.apply(params, tokens)
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1], tokens[:, 1:]))

    (got, aux), g_got = jax.value_and_grad(causal_lm_loss(model), has_aux=True)(params, tokens)
    want, g_want = jax.value_and_grad(plain)(params)
    assert got == aux["ce"] and rel(got, want) < F32
    for (path, a), b in zip(jax.tree.leaves_with_path(g_got), jax.tree.leaves(g_want)):
        assert rel(a, b) < 5 * F32, jax.tree_util.keystr(path)


# -- today's defaults, and the published configuration ------------------------------------


def test_default_transformer_is_bit_for_bit_the_model_it_was():
    """The parameter tree and the numbers of the pre-LN, learned-position,
    SwiGLU model, written out here as the module computed them before the
    architecture became fields."""
    import flax.linen as nn

    from heat_tpu.parallel import local_attention

    lm = TransformerLM(vocab_size=50, d_model=32, num_heads=4, num_layers=2, max_len=16)
    toks = jnp.arange(24, dtype=jnp.int32).reshape(2, 12) % 50
    params = lm.init(jax.random.PRNGKey(0), toks)
    assert set(params) == {"params"}
    p = params["params"]
    assert set(p) == {"embed", "pos", "block0", "block1", "ln_f", "lm_head"}
    assert set(p["block0"]) == {"ln1", "attn", "ln2", "gate", "up", "down"}
    assert set(p["block0"]["attn"]) == {"query", "key", "value", "out"}
    assert set(p["block0"]["ln1"]) == {"scale", "bias"}
    assert p["block0"]["gate"]["kernel"].shape == (32, 128)

    def ln(q, x):
        return nn.LayerNorm().apply({"params": q}, x)

    x = p["embed"]["embedding"][toks] + p["pos"]["embedding"][jnp.arange(12)][None]
    for i in range(2):
        b = p[f"block{i}"]
        h = ln(b["ln1"], x)
        proj = lambda name: jax.lax.dot_general(  # noqa: E731
            h, b["attn"][name]["kernel"], (((2,), (0,)), ((), ()))
        )
        o = local_attention(proj("query"), proj("key"), proj("value"), causal=True, block_size=512)
        x = x + jax.lax.dot_general(o, b["attn"]["out"]["kernel"], (((2, 3), (0, 1)), ((), ())))
        h = ln(b["ln2"], x)
        h = nn.silu(h @ b["gate"]["kernel"]) * (h @ b["up"]["kernel"])
        x = x + h @ b["down"]["kernel"]
    want = ln(p["ln_f"], x) @ p["lm_head"]["kernel"]
    np.testing.assert_array_equal(np.asarray(lm.apply(params, toks)), np.asarray(want))


def test_olmoe_1b_7b_is_the_published_configuration():
    """Field for field what the catalog's row and the configuration's file
    say; 625.6 M parameters at one layer, 6.92 B at sixteen."""
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "chipbench", "configs", "olmoe-1b-7b-train.json")) as f:
        cfg = json.load(f)
    m = olmoe_1b_7b()
    assert (m.d_model, m.num_heads, m.num_layers, m.vocab_size, m.max_len) == (2048, 16, 16, 50304, 4096)
    assert (m.num_experts, m.experts_per_token, m.d_ff) == (64, 8, 1024)
    assert (m.norm, m.norm_eps, m.positions, m.rope_theta, m.qk_norm, m.ffn) == (
        "rmsnorm", 1e-5, "rope", 10000.0, True, "moe",
    )
    assert (m.dtype, m.accum_dtype, m.attn_impl) == (jnp.bfloat16, jnp.float32, "flash")
    for key, field in (
        ("hidden_size", "d_model"), ("num_attention_heads", "num_heads"), ("vocab_size", "vocab_size"),
        ("num_experts", "num_experts"), ("num_experts_per_tok", "experts_per_token"),
        ("intermediate_size", "d_ff"), ("max_position_embeddings", "max_len"),
        ("rms_norm_eps", "norm_eps"), ("rope_theta", "rope_theta"),
    ):
        assert cfg[key] == getattr(m, field), key
    assert cfg["num_hidden_layers"] == 1 and cfg["num_key_value_heads"] == cfg["num_attention_heads"]

    def count(layers):
        shapes = jax.eval_shape(
            lambda: olmoe_1b_7b(num_layers=layers, attn_impl="local").init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            )
        )["params"]
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))

    assert count(1) == 625_616_896
    assert count(16) - count(1) == 15 * (count(2) - count(1)) and 6.9e9 < count(16) < 6.93e9


# -- mixed precision: the guarantee holds, the control does not ---------------------------

# bfloat16 operands cost 2^-9 a product: through a dozen products the logits
# come out within 3.0e-3..3.2e-3 (root mean square over that of the logits)
# and 3.2e-3..4.1e-3 (largest over largest) of the reference on five seeds.
# The control (a bfloat16 accumulator, norms and router softmax) reads
# 5.7e-3..6.6e-3 and 7.0e-3..1.3e-2: the limits lie between. The loss does
# not tell them apart (1e-5 both: the mean over positions averages rounding out).
MIXED_RMS, MIXED_MAX = 4.3e-3, 5.5e-3


def mixed(**fields):
    return tiny(dtype=jnp.bfloat16, accum_dtype=jnp.float32, **fields)


def test_mixed_precision_stays_inside_the_limits_and_the_control_does_not(weights, tokens):
    params = lm_step.to_system(weights, 4)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.logits_of(weights, tokens, C)
        want_loss, _ = ref.loss_parts(weights, tokens, C, COEF)
        control, _ = ref.logits_of(weights, tokens, C, "bf16")
    got = mixed().apply(params, tokens)
    loss, _ = causal_lm_loss(mixed(), load_balance_coef=0.01, router_z_coef=0.001)(params, tokens)
    assert got.dtype == jnp.float32
    assert ref.rms_gap(got, want) < MIXED_RMS and rel(got, want) < MIXED_MAX
    assert rel(loss, want_loss) < 1e-4
    assert ref.rms_gap(control, want) > 1.2 * MIXED_RMS and rel(control, want) > 1.2 * MIXED_MAX


@highest
def test_the_reference_can_be_held_to_a_given_routing(weights, tokens):
    """``forced``: the reference on the experts another computation chose, so
    that a comparison reads arithmetic and not a choice between near ties. Its
    own choice forced on it changes nothing; another choice changes the result
    and takes each expert at the reference's own probability."""
    loss, parts = ref.loss_parts(weights, tokens, C, COEF)
    again, same = ref.loss_parts(weights, tokens, C, COEF, forced=parts["chosen"])
    assert float(again) == float(loss)
    np.testing.assert_array_equal(same["expert_counts"], parts["expert_counts"])
    other = (parts["chosen"] + 1) % 8
    moved, diff = ref.loss_parts(weights, tokens, C, COEF, forced=other)
    np.testing.assert_array_equal(diff["chosen"], other)
    assert float(moved) != float(loss) and int(diff["expert_counts"].sum()) == 2 * T * 2
    grads = jax.grad(lambda p: ref.loss_parts(p, tokens, C, COEF, forced=other)[0])(weights)
    assert float(jnp.abs(grads["layers"][0]["wr"]).max()) > 0  # the weights still reach the router


def test_a_bfloat16_router_changes_the_chosen_experts(weights, tokens):
    """The router's product, softmax and top-k stay float32 under bfloat16
    operands: the mixed model chooses what the float32 model chooses wherever
    the float32 reference's candidates are not within rounding of each other;
    the control's bfloat16 softmax does not."""
    params = lm_step.to_system(weights, 4)
    with jax.default_matmul_precision("highest"):
        _, parts = ref.loss_parts(weights, tokens, C, COEF)
        _, low = ref.loss_parts(weights, tokens, C, COEF, "bf16")
    _, sown = mixed().apply(params, tokens, mutable=["aux"])
    chosen = np.asarray(sown["aux"]["block0"]["moe"]["moe"][0]["chosen"])
    probs = np.asarray(parts["probs"][0])
    assert ref.routing_disagreement(chosen, probs, 2, 0.01) == 0.0
    # with no slack for rounding, the control's choices are not the reference's
    assert ref.routing_disagreement(np.asarray(low["chosen"][0]), probs, 2, 0.0) > 0.0
    assert ref.routing_disagreement(np.asarray(parts["chosen"][0]), probs, 2, 0.0) == 0.0


# -- queries and keys to the flash kernels in one pass (PR 50) ------------------------------


@highest
def test_the_pass_before_the_flash_kernels_is_the_xla_lines_and_holds_the_same_parameters(weights, tokens, monkeypatch):
    """``nn/pallas_qk_prep.py`` in the interpreter: the norm over all of hidden
    and rotary, two layers."""
    from tests.test_pallas_qk_prep import both_forms

    model = tiny(2)
    (logits, grads, passes), (k_logits, k_grads, _) = both_forms(
        monkeypatch, model, lm_step.to_system(ref.init_params(SEED, {**C, "num_hidden_layers": 2}), 4), tokens,
        causal_lm_loss(model, load_balance_coef=0.01, router_z_coef=0.001),
    )
    assert passes["xla"] >= 4
    assert rel(k_logits, logits) < F32
    for (path, g), w in zip(jax.tree.leaves_with_path(k_grads["params"]), jax.tree.leaves(grads["params"])):
        assert rel(g, w) < 5 * F32, jax.tree_util.keystr(path)
