"""LFM2-24B-A2B through ``ht.nn`` against its plain reference
(``chipbench/references/lfm2_plain.py``), on the CPU at tiny widths with seeded
weights: (a) the whole model, logits, loss, every group's gradients, with
``local`` and ``flash`` attention (the Pallas interpreter; heads of 12, no lane
multiple), in float32 and in mixed precision with the control failing; (b) two
steps of ``make_train_step`` with its rule-moved biases against the reference's
AdamW and bias rule; (c) the gated short convolution's written-out backward pass
against autodiff of the plain form, and its causality; (d) the tied head;
(e) the shares of an expert layer add up to the uncut layer; (f) the
normalisation's epsilon as a field; (g) the layer pattern, the published
configuration, the counters and the scopes. A CPU run gives results and counts,
no time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from chipbench.kinds import lfm2_step
from chipbench.references import lfm2_plain as ref
from heat_tpu import telemetry
from heat_tpu.nn import (
    DataParallel, DroplessMoE, TransformerBlock, TransformerLM, balance_bias_rule, causal_lm_loss, lfm2_24b_a2b,
    read_routing,
)
from heat_tpu.nn.deltanet import causal_depthwise_conv, gated_short_conv

# published blocks 1..5: conv-dense, attention, conv, conv, conv (one dense + four expert blocks)
C = dict(
    hidden_size=48, num_attention_heads=4, num_key_value_heads=2, norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    layer_types=["conv", "conv", "full_attention", "conv"] * 2, first_block=1, conv_L_cache=3,
    intermediate_size=80, num_dense_layers=1, num_experts=16, num_experts_per_tok=3, num_experts_held=4,
    first_expert_held=4, moe_intermediate_size=16, norm_topk_prob=True, routed_scaling_factor=1,
    vocab_size=97, num_hidden_layers=5, bias_rate=0.001,
)
COEF = {"load_balance": 0.0, "router_z": 0.0}
OPT = {"lr": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip": 1.0,
       "warmup_steps": 4, "coef": COEF}
SEED, T = 23, 40

# float32 against float32 at "highest": the same sums in another order, each term
# rounded once (6e-8), through five blocks; observed 2e-7..3e-6
F32 = 2e-5


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    yield
    jax.clear_caches()


def tiny(**fields):
    arch = dict(
        num_layers=5, first_block=1, experts_held=(4, 4), vocab_size=97, d_model=48, num_heads=4, num_kv_heads=2,
        head_dim=12, dense_d_ff=80, d_ff=16, num_experts=16, experts_per_token=3, max_len=64,
        dtype=jnp.float32, accum_dtype=None, attn_impl="local", block_size=16,
    )
    return lfm2_24b_a2b(**{**arch, **fields})


@pytest.fixture(scope="module")
def weights():
    # norm gains and biases away from their initial 1 and 0, so that a gain applied in the
    # wrong place, or a bias that leaks into the weights, shows
    w = ref.init_params(SEED, C, 0.15, 0.1)
    key = jax.random.PRNGKey(SEED)
    leaves, tree = jax.tree.flatten(w)
    leaves = [
        a + 0.2 * jax.random.normal(jax.random.fold_in(key, i), a.shape, jnp.float32) if a.ndim == 1 else a
        for i, a in enumerate(leaves)
    ]
    w = jax.tree.unflatten(tree, leaves)
    w["bias"] = 0.05 * jax.random.normal(jax.random.fold_in(key, 999), w["bias"].shape, jnp.float32)
    return w


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(ref.batch(SEED, 0, 2, T, ref.zipf_cdf(97)))


def highest(fn):
    @functools.wraps(fn)
    def run(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)

    return run


rel = ref.rel_gap


# -- (a) the whole model -------------------------------------------------------------------


@highest
@pytest.mark.parametrize("impl", ["local", "flash"])
def test_forward_logits_loss_and_parts_match_the_reference(weights, tokens, impl):
    params = lfm2_step.to_system(weights, C)
    model = tiny(attn_impl=impl)
    want, _ = ref.logits_of(weights, tokens, C)
    got = model.apply(params, tokens)
    assert got.shape == (2, T, 97) and rel(got, want) < F32
    loss, aux = causal_lm_loss(model)(params, tokens)
    want_loss, parts = ref.loss_parts(weights, tokens, C, COEF)
    assert rel(loss, want_loss) < F32
    for name in ("ce", "load_balance", "router_z"):
        assert rel(aux[name], parts[name]) < F32, name
    np.testing.assert_array_equal(aux["expert_counts"], parts["expert_counts"])
    assert aux["expert_counts"].shape == (4, 16)  # the four expert layers; the dense block has no router
    held = int(parts["expert_counts"][:, 4:8].sum())
    assert int(aux["assignments_due"]) == int(aux["assignments_computed"]) == held
    assert aux["assignments_routed"] == 4 * 2 * T * 3
    assert rel(aux["route_bias_max_abs"], jnp.max(jnp.abs(weights["bias"]))) < 1e-7


@highest
@pytest.mark.parametrize("impl", ["local", "flash"])
def test_gradients_of_every_parameter_group(weights, tokens, impl):
    loss_fn = causal_lm_loss(tiny(attn_impl=impl))
    grads = jax.grad(lambda p: loss_fn(p, tokens)[0])(lfm2_step.to_system(weights, C))
    want = jax.grad(lambda p: ref.loss_parts(p, tokens, C, COEF)[0])(weights)
    got = lfm2_step.from_system(grads)
    for (path, g), w in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(want)):
        # back through five blocks: observed up to 2e-5 (a head norm's gain)
        assert g.shape == w.shape and rel(g, w) < 1e-4, jax.tree_util.keystr(path)
    assert not np.any(np.asarray(got["bias"]))  # no gradient reaches the bias
    g_norms, w_norms = ref.group_norms(got), ref.group_norms(want)
    assert set(g_norms) == set(ref.GROUPS) and "head" not in ref.GROUPS  # the head has no matrix of its own
    for group in ref.GROUPS:
        assert float(w_norms[group]) > 0 and rel(g_norms[group], w_norms[group]) < 1e-4, group


@highest
def test_the_references_written_out_backward_pass_is_autodiff(weights, tokens):
    """``lfm2_plain._gradients`` (a block a program, the tied head's two parts
    added by hand) against ``jax.grad`` of ``lfm2_plain.loss_parts``."""
    loss, parts, grads = ref._gradients(weights, tokens, C, COEF, "float32", ref._free_choice(C, tokens))
    (want_loss, want_parts), want = jax.value_and_grad(ref.loss_parts, has_aux=True)(weights, tokens, C, COEF)
    assert rel(loss, want_loss) < 1e-6
    np.testing.assert_array_equal(parts["chosen"], want_parts["chosen"])
    for (path, g), w in zip(jax.tree.leaves_with_path(grads), jax.tree.leaves(want)):
        assert rel(g, w) < 1e-5 or not np.any(np.asarray(w)), jax.tree_util.keystr(path)
    # the control: the table's gradient without the head's product is another gradient
    _, _, untied = ref._gradients(weights, tokens, {**C, "untied_head": True}, COEF, "float32", ref._free_choice(C, tokens))
    assert rel(untied["embed"], want["embed"]) > 0.1
    # and one tap short is another model
    short, _ = ref.logits_of(weights, tokens, {**C, "conv_taps_used": 2})
    assert ref.rms_gap(short, ref.logits_of(weights, tokens, C)[0]) > 0.05


def test_mixed_precision_is_near_the_reference_and_the_control_is_farther(tokens):
    """bfloat16 operands with float32 accumulation, as the cell runs, at the
    cell's initialisation: near the float32 reference held to the same routing;
    the reference with bfloat16 everywhere is farther from it than the program."""
    weights = ref.init_params(SEED, C, 0.02, 0.02 / 80**0.5)
    params = lfm2_step.to_system(weights, C)
    model = tiny(dtype=jnp.bfloat16, accum_dtype=jnp.float32)
    got, sown = model.apply(params, tokens, mutable=["aux"])
    chosen = jnp.stack([sown["aux"][f"block{i}"]["moe"]["moe"][0]["chosen"] for i in model.expert_layers()])
    with jax.default_matmul_precision("highest"):
        _, parts = ref.loss_parts(weights, tokens, C, COEF)
        want, _ = ref.logits_of(weights, tokens, C, forced=chosen)
        control, _ = ref.logits_of(weights, tokens, C, "bf16", forced=chosen)
    assert got.dtype == jnp.float32
    # bfloat16 operands (2^-9 each) through five blocks: observed 2.0e-3; the control 3.6e-3
    assert ref.rms_gap(got, want) < 2.8e-3 < ref.rms_gap(control, want)
    for i in range(4):  # nearly every choice of the mixed model is one the float32 reference could have made
        assert ref.routing_disagreement(np.asarray(chosen[i]), np.asarray(parts["probs"][i]), 3, 0.01) <= 0.02


# -- (b) steps ----------------------------------------------------------------------------


@highest
def test_two_steps_with_the_bias_rule_match_the_references_adamw(weights, tokens):
    comm = ht.MeshCommunication(devices=jax.devices()[:1])
    model = tiny(comm=comm, remat=True)
    opt = lfm2_step.optimizer(OPT)
    dp = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True)
    step = dp.make_train_step(causal_lm_loss(model), has_aux=True, state_rule=balance_bias_rule(C["bias_rate"]))
    params = jax.tree.map(jnp.copy, lfm2_step.to_system(weights, C))
    state = opt.init({"params": params["params"]})
    want, want_state = jax.tree.map(jnp.copy, weights), ref.adamw_init(weights)
    for i in range(2):
        batch = jnp.asarray(ref.batch(SEED, i, 2, T, ref.zipf_cdf(97)))
        params, state, loss, aux = step(params, state, batch)
        loss, aux = read_routing(loss, aux)
        want, want_state, want_loss, parts = ref.train_step(want, want_state, batch, C, OPT)
        assert rel(loss, want_loss) < F32
        np.testing.assert_array_equal(aux["expert_counts"], parts["expert_counts"])
    got = lfm2_step.from_system(params)
    # the rule moved every bias by exactly the rate, twice, from the counts both sides agree on
    np.testing.assert_allclose(got["bias"], want["bias"], rtol=0, atol=1e-9)
    assert float(jnp.max(jnp.abs(got["bias"] - weights["bias"]))) == pytest.approx(2 * C["bias_rate"], rel=1e-4)
    for (path, g), w, before in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(want), jax.tree.leaves(weights)):
        # Adam's m / sqrt(v) turns a gradient entry's rounding into a share of the update: the gap is held
        # against the update's own size (observed under 2e-3 of it)
        moved = float(jnp.sqrt(jnp.sum((w - before) ** 2)))
        assert float(jnp.sqrt(jnp.sum((g - w) ** 2))) <= 1e-2 * moved + 1e-9, jax.tree_util.keystr(path)
    assert "lm_head" not in params["params"] and "embed" in params["params"]


# -- (c) the gated short convolution -------------------------------------------------------


def _conv_inputs(taps=3, shape=(2, 19, 10)):
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    b, c, x, g = (jax.random.normal(k[i], shape, jnp.float32) for i in (0, 1, 2, 4))
    return b, c, x, jax.random.normal(k[3], (shape[-1], taps), jnp.float32), g


@pytest.mark.parametrize("taps", [2, 3, 4])
def test_the_written_out_backward_pass_is_autodiff_of_the_plain_form(taps):
    b, c, x, w, g = _conv_inputs(taps)
    plain = lambda b, c, x, w: c * causal_depthwise_conv(b * x, w)  # noqa: E731
    out, pull = jax.vjp(gated_short_conv, b, c, x, w)
    want, pull_plain = jax.vjp(plain, b, c, x, w)
    # float32 sums of at most 2 x 19 terms in another order
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    for got, wanted in zip(pull(g), pull_plain(g)):
        np.testing.assert_allclose(got, wanted, rtol=1e-5, atol=1e-5)
    # and against the reference's shifted sum
    np.testing.assert_allclose(out, ref.short_conv(ref._Numerics("float32"), b, c, x, w), rtol=1e-6, atol=1e-6)
    want_all = ref.conv_and_gradients(b, c, x, w, g)
    for got, wanted in zip((out,) + pull(g), want_all):
        np.testing.assert_allclose(got, wanted, rtol=1e-5, atol=1e-5)


def test_the_backward_pass_keeps_its_four_inputs_alone():
    b, c, x, w, _ = _conv_inputs()
    from jax._src.ad_checkpoint import saved_residuals

    kept = saved_residuals(gated_short_conv, b, c, x, w)
    shapes = sorted(tuple(a.shape) for a, _ in kept)
    assert shapes == sorted([b.shape, c.shape, x.shape, w.shape])  # no shifted copy, no gated input, no pre-gate output


def test_bfloat16_inputs_give_a_float32_result_and_cotangents_of_their_own_dtype():
    b, c, x, w, g = _conv_inputs()
    low = [a.astype(jnp.bfloat16) for a in (b, c, x)]
    out, pull = jax.vjp(gated_short_conv, *low, w)
    assert out.dtype == jnp.float32
    assert [a.dtype for a in pull(g)] == [jnp.bfloat16] * 3 + [jnp.float32]


def test_a_position_sees_itself_and_the_two_before_it():
    b, c, x, w, _ = _conv_inputs()
    out = gated_short_conv(b, c, x, w)
    t0 = 9
    later = (jnp.arange(x.shape[1]) > t0)[None, :, None]
    moved = gated_short_conv(*(jnp.where(later, a + 1.0, a) for a in (b, c, x)), w)
    np.testing.assert_array_equal(out[:, :t0 + 1], moved[:, :t0 + 1])  # bit for bit: nothing after t0 is read
    assert np.all(np.asarray(out[:, t0 + 1:]) != np.asarray(moved[:, t0 + 1:]))
    # position t reads t - 2, t - 1, t: moving x at t - 3 alone leaves it, moving t - 2 does not
    at = lambda t: jnp.where((jnp.arange(x.shape[1]) == t)[None, :, None], x + 1.0, x)  # noqa: E731
    np.testing.assert_array_equal(gated_short_conv(b, c, at(t0 - 3), w)[:, t0], out[:, t0])
    assert np.all(np.asarray(gated_short_conv(b, c, at(t0 - 2), w)[:, t0]) != np.asarray(out[:, t0]))
    # the first position sees zeros before it: its output is the last tap's alone
    np.testing.assert_allclose(out[:, 0], c[:, 0] * w[:, -1] * b[:, 0] * x[:, 0], rtol=1e-6)


# -- (d) the tied head ---------------------------------------------------------------------


@highest
def test_the_tables_gradient_is_the_gathers_plus_the_heads(weights, tokens):
    model = tiny()
    params = lfm2_step.to_system(weights, C)
    assert "lm_head" not in model.init(jax.random.PRNGKey(0), tokens)["params"]
    grads = jax.grad(lambda p: causal_lm_loss(model)(p, tokens)[0])(params)
    table = grads["params"]["embed"]["embedding"]

    # the same model untied, its head a copy of the table: the two gradients it keeps apart add up to the tied one
    untied = tiny(tie_embeddings=False)
    free = {**params, "params": {**params["params"], "lm_head": {"kernel": weights["embed"].T}}}
    np.testing.assert_allclose(untied.apply(free, tokens), model.apply(params, tokens), rtol=1e-6, atol=1e-6)
    parts = jax.grad(lambda p: causal_lm_loss(untied)(p, tokens)[0])(free)["params"]
    gathered, head = parts["embed"]["embedding"], parts["lm_head"]["kernel"].T
    assert rel(table, gathered + head) < 1e-5
    seen = np.unique(np.asarray(tokens))
    rows = np.setdiff1d(np.arange(97), seen)
    assert not np.any(np.asarray(gathered)[rows]) and np.any(np.asarray(head)[rows])  # sparse rows, a dense product
    assert rel(table, gathered) > 0.1 and rel(table, head) > 1e-3


def test_tied_logits_take_the_products_dtype_and_the_loss_counts_the_tie(tokens):
    model = tiny(dtype=jnp.bfloat16, accum_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(1), tokens)
    params = {k: v for k, v in params.items() if k != "aux"}
    hidden = model.apply(params, tokens, head=False, mutable=["aux"])[0]
    assert hidden.shape == (2, T, 48) and hidden.dtype == jnp.float32
    logits = model.apply(params, tokens, mutable=["aux"])[0]
    want = jnp.dot(hidden.astype(jnp.bfloat16), params["params"]["embed"]["embedding"].astype(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(logits, want)
    counters = telemetry.get_registry().counters
    before = counters["lm.head.tied"]
    text = jax.jit(causal_lm_loss(model)).lower(params, tokens).as_text(debug_info=True)
    assert counters["lm.head.tied"] == before + 1
    assert "lm.tied_head" in text and "conv.mix" in text and "conv.project" in text


# -- (e) the shares add up -----------------------------------------------------------------


@highest
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """16 experts over four ranks of four (the cell: 64 over eight of eight):
    the parts that the four shares give, each from its own four experts' weights,
    add up to what the uncut reference gives for the whole layer; so do the
    program's shares, and no shared expert is there to count once."""
    whole = {**C, "num_experts_held": 16, "first_expert_held": 0}
    key = jax.random.PRNGKey(3)
    lp = {name: 0.3 * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) for i, (name, shape) in enumerate(
        {"wr": (48, 16), "wg": (16, 48, 16), "wu": (16, 48, 16), "wd": (16, 16, 48)}.items())}
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 9), (16,), jnp.float32)
    h = jax.random.normal(jax.random.fold_in(key, 10), (64, 48), jnp.float32)
    want, counts = ref.experts_layer(whole, lp, bias, h)
    total_ref, total_sys = jnp.zeros_like(want), jnp.zeros_like(want)
    for first in range(0, 16, 4):
        share = {**C, "num_experts_held": 4, "first_expert_held": first}
        mine = {"wr": lp["wr"], **{k: lp[k][first:first + 4] for k in ("wg", "wu", "wd")}}
        part, share_counts = ref.experts_layer(share, mine, bias, h)
        np.testing.assert_array_equal(share_counts, counts)  # every share routes over all sixteen
        total_ref = total_ref + part
        layer = DroplessMoE(16, 3, 16, norm_topk=True, norm_topk_eps=1e-6, score="sigmoid", select_bias=True,
                            experts_held=(first, 4))
        tree = {"params": {"router": lp["wr"], "w_gate": mine["wg"], "w_up": mine["wu"], "w_down": mine["wd"]},
                "route_bias": {"bias": bias}}
        got, _ = layer.apply(tree, h[None], mutable=["aux"])
        assert rel(got[0], part) < F32, first
        total_sys = total_sys + got[0]
    # four float32 partial sums against one sum of sixteen terms in another order
    assert rel(total_ref, want) < F32 and rel(total_sys, want) < F32
    assert float(jnp.max(jnp.abs(want))) > 0.01


# -- (f) the normalisation's epsilon --------------------------------------------------------


def test_the_epsilon_is_a_field_whose_default_is_the_literal_it_replaces():
    """``DroplessMoE`` divided a sigmoid router's top-k weights by their sum +
    1e-20; the field's default gives the same program and bit-equal weights
    (Trinity-Mini's), and 1e-6 (this family's) gives others."""
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 32), jnp.float32)
    make = lambda **kw: DroplessMoE(16, 4, 8, norm_topk=True, score="sigmoid", select_bias=True, route_scale=2.826, **kw)  # noqa: E731
    default, literal, family = make(), make(norm_topk_eps=1e-20), make(norm_topk_eps=1e-6)
    tree = default.init(jax.random.PRNGKey(0), h)
    tree = {k: v for k, v in tree.items() if k != "aux"}
    weights = lambda layer: layer.apply(tree, h, mutable=["aux"])[1]["aux"]["moe"][0]["weights"]  # noqa: E731
    np.testing.assert_array_equal(weights(default), weights(literal))
    jaxpr = lambda layer: str(jax.make_jaxpr(lambda t, x: layer.apply(t, x, mutable=["aux"]))(tree, h))  # noqa: E731
    assert jaxpr(default) == jaxpr(literal) != jaxpr(family)
    assert not np.array_equal(weights(default), weights(family))
    w = np.asarray(weights(family), np.float64) / 2.826
    assert np.all(w.sum(-1) < 1.0) and np.all(w.sum(-1) > 1.0 - 1e-5)  # s / (sum s + 1e-6): a hair under 1
    assert DroplessMoE.norm_topk_eps == 1e-20 and TransformerLM.norm_topk_eps == 1e-20
    assert lfm2_24b_a2b().norm_topk_eps == 1e-6 and ht.nn.trinity_mini().norm_topk_eps == 1e-20


# -- (f2) the held experts' first window ----------------------------------------------------


def _held_layer(**kw):
    return DroplessMoE(16, 3, 16, norm_topk=True, norm_topk_eps=1e-6, score="sigmoid", select_bias=True, experts_held=(4, 4), **kw)


@highest
def test_the_first_window_is_a_field_whose_default_is_the_two_shares_it_was():
    """``held_window`` even shares of the assignments: at its default the
    program is the one it was; a window as long as the assignments leaves no
    branch and no further window; whatever the window, every held assignment
    is computed and the result and its gradients are the same."""
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 48), jnp.float32)
    default, two, five, whole = _held_layer(), _held_layer(held_window=2.0), _held_layer(held_window=2.5), _held_layer(held_window=4.0)
    tree = {k: v for k, v in default.init(jax.random.PRNGKey(0), h).items() if k != "aux"}
    # a routing far from even: the four held experts take every token's first choice
    tree["route_bias"] = {"bias": jnp.zeros(16).at[4:8].set(1.0)}
    jaxpr = lambda layer: str(jax.make_jaxpr(lambda t, x: layer.apply(t, x, mutable=["aux"]))(tree, h))  # noqa: E731
    assert jaxpr(default) == jaxpr(two) != jaxpr(five)
    assert "cond[" in jaxpr(default) and "cond[" in jaxpr(five) and "cond[" not in jaxpr(whole)  # 4 x 4/16 of the assignments: all
    assert DroplessMoE.held_window == TransformerBlock.held_window == TransformerLM.held_window == 2.0
    assert lfm2_24b_a2b().held_window == 2.0 and lfm2_24b_a2b(held_window=5.0).held_window == 5.0

    def run(layer):
        def f(params, x):
            out, state = layer.apply({**tree, "params": params}, x, mutable=["aux"])
            return jnp.sum(out * jnp.cos(out)), (out, state["aux"]["moe"][0])

        (_, (out, aux)), grads = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(tree["params"], h)
        return out, aux, grads

    want, aux, want_grads = run(default)
    held = int(aux["held"])
    assert held > 2 * (64 * 3 * 4 // 16) and int(aux["computed"]) == held  # past two even shares: the further windows ran
    for layer in (five, whole):
        out, aux, grads = run(layer)
        assert int(aux["computed"]) == held
        # the same rows in windows cut elsewhere: float32 sums in another order
        assert rel(out, want) < F32
        assert all(rel(g, w) < F32 for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)))


def test_the_taps_are_drawn_as_the_cell_draws_them():
    """``GatedShortConv``'s own draw is uniform in +-1/sqrt(taps), what the
    benchmark's ``init_params`` draws: a model built by the builder starts where
    the cell measured."""
    from heat_tpu.nn import GatedShortConv

    for taps in (3, 4):
        w = np.asarray(GatedShortConv(taps).init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 256)))["params"]["conv"])
        assert w.shape == (256, taps) and 0.97 * taps**-0.5 < np.abs(w).max() <= taps**-0.5
        assert abs(w.std() - (3 * taps) ** -0.5) < 0.02  # a uniform draw's deviation: its edge over sqrt(3)
    mine = np.asarray(ref.init_params(SEED, C)["layers"][0]["w_conv"])
    assert np.abs(mine).max() <= 3**-0.5 and abs(mine.std() - 1 / 3) < 0.03


# -- (f3) one update, entry by entry --------------------------------------------------------


def test_the_update_is_looked_at_entry_by_entry():
    """``leaf_look``: of an optimizer that moves a share of a leaf's entries
    the other way (what another compilation's routing does to AdamW's first
    steps) it says how many were turned and how large their gradients are; one
    that misses every entry by a tenth of the update turns none; the expert
    layers' leaves are told from the others."""
    key = jax.random.PRNGKey(11)
    old = jax.random.normal(key, (64, 32))
    g = jax.random.normal(jax.random.fold_in(key, 1), (64, 32))
    new = old - 1e-3 * jnp.sign(g)
    size = jnp.abs(g) / jnp.sqrt(jnp.mean(g**2))
    small = size < 0.05
    look = ref.leaf_look(old, new, jnp.where(small, old + 1e-3 * jnp.sign(g), new), g)
    share = float(jnp.mean(small))
    assert 0.02 < share < 0.06 and look["turned_share"] == pytest.approx(share) == look["sign_turned_share"]
    assert 0.04 < look["turned_largest_gradient"] <= 0.05 and 0 < look["turned_median_gradient"] < look["turned_largest_gradient"]
    tenth = ref.leaf_look(old, new, new + 0.1 * (new - old), g)
    assert tenth["turned_share"] == 0.0 == tenth["sign_turned_share"] and tenth["turned_largest_gradient"] is None
    assert ref.leaf_look(old, new, old, g)["turned_share"] == 1.0  # a step that does not update
    # an expert block's router, experts and the gain of the norm they read; not its mixer, nor the dense block's gain
    tree = {"embed": 0, "g_f": 0, "layers": [{"w_in": 0, "g_c": 0, "wf_g": 0}, {"wq": 0, "g_a": 0, "g_c": 0, "wr": 0, "wg": 0, "wu": 0, "wd": 0}]}
    assert ref.routed(tree) == {(1, "wr"), (1, "wg"), (1, "wu"), (1, "wd"), (1, "g_c")}


# -- (g) the pattern, the published sizes, the counters -------------------------------------


def test_the_builder_is_the_published_configuration():
    m = lfm2_24b_a2b()
    assert (m.num_layers, m.d_model, m.num_heads, m.num_kv_heads, m.head_dim) == (40, 2048, 32, 8, 64)
    kinds = [m.mixer_of(i) for i in range(40)]
    assert kinds == ["shortconv", "shortconv", "attention", "shortconv"] * 10 and kinds.count("attention") == 10
    assert (m.dense_layers, m.dense_d_ff, m.d_ff, m.num_experts, m.experts_per_token) == (2, 11776, 1536, 64, 4)
    assert m.expert_layers() == tuple(range(2, 40)) and m.conv_taps == 3 and m.tie_embeddings
    assert (m.router_score, m.router_bias, m.norm_topk, m.route_scale, m.shared_d_ff) == ("sigmoid", True, True, 1.0, 0)
    assert (m.norm, m.norm_eps, m.qk_norm, m.rope_theta, m.vocab_size, m.max_len) == ("rmsnorm", 1e-5, "head", 1e6, 65536, 128000)
    assert not m.sandwich_norm and m.embed_scale is None and not m.attn_gate and m.windows == (None,)
    # the cell's stage: published blocks 1..7 = conv-dense, attention, conv, conv, conv, attention, conv
    cut = lfm2_24b_a2b(num_layers=7, first_block=1, experts_held=(0, 8), vocab_size=8192)
    assert [cut.mixer_of(i) for i in range(7)] == [kinds[i] for i in range(1, 8)] and cut.dense_layers == 1
    assert lfm2_24b_a2b(num_layers=4, first_block=2).dense_layers == 0


def test_the_cut_holds_the_issues_parameter_count():
    cut = lfm2_24b_a2b(num_layers=7, first_block=1, experts_held=(0, 8), vocab_size=8192)
    shapes = jax.eval_shape(lambda: cut.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    p = shapes["params"]
    assert count(p["block0"]["conv"]) == 16_783_360 and count(p["block1"]["attn"]) == 10_485_888
    assert count(p["block0"]) == 89_139_200 and count(p["block2"]) == 92_416_000 and count(p["block1"]) == 86_118_528
    assert count(p["embed"]) + count(p["ln_f"]) == 16_779_264 and "lm_head" not in p
    assert count(p) == 647_819_520  # 10.37 GB at 16 bytes a parameter
    assert count(shapes["route_bias"]) == 6 * 64


def test_an_unknown_mixer_is_refused_with_the_three_that_exist():
    with pytest.raises(ValueError, match=r"'attention', 'deltanet', 'shortconv'"):
        TransformerBlock(4, mixer="hyena").init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))
    with pytest.raises(ValueError, match="mixer must be one of"):
        TransformerLM(11, 8, 2, 1, mixers=("conv",)).init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_counters_say_what_a_trace_built(tokens):
    counters = telemetry.get_registry().counters
    before = {k: counters[k] for k in ("conv.mixers", "attn.lanes_padded", "lm.head.tied")}
    model = tiny(attn_impl="flash", block_size=None)
    params = {k: v for k, v in model.init(jax.random.PRNGKey(1), tokens).items() if k != "aux"}
    assert counters["conv.mixers"] - before["conv.mixers"] == 4  # blocks 0, 2, 3, 4
    added = counters["attn.lanes_padded"] - before["attn.lanes_padded"]
    assert added == 128 - 12  # one attention block's forward kernel: heads of 12 padded to the 128 lanes
    jax.grad(lambda p: causal_lm_loss(model)(p, tokens)[0])(params)
    assert counters["lm.head.tied"] == before["lm.head.tied"] + 1
    # a head of whole lanes pads nothing
    mark = counters["attn.lanes_padded"]
    from heat_tpu.parallel import flash_attention

    q = jnp.zeros((1, 16, 2, 128), jnp.float32)
    flash_attention(q, q, q, causal=True)
    assert counters["attn.lanes_padded"] == mark
