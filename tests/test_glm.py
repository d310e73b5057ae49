"""GLM-4.7-Flash through ``ht.nn`` against its plain reference
(``chipbench/references/glm_plain.py``), on the CPU at tiny widths with seeded
weights: (a) the whole model, the trunk's and the prediction module's logits,
both losses, every group's gradients (the head's and the table's as the sums of
their two uses), with ``local`` and ``flash`` attention (the Pallas interpreter;
3 heads of 10 + 6, ranks of 20 and 12: no lane multiples), in float32 and in
mixed precision with the control farther; (b) two steps of ``make_train_step``
with its rule-moved biases, the module's among them, against the reference's
AdamW and bias rule; (c) the latent mixer alone against the written-out form,
and the one rotary key's gradient as the sum over the heads; (d) the module's
rolled form against the reference's ``T - 1`` positions, and the leak probe;
(e) the shares of an expert layer add up to the uncut layer at scale 1.8, the
shared expert counted once; (f) defaults leave the four accepted models'
programs as they are; (g) the builder, the parameter count of the cut, the
counters and the scopes. A CPU run gives results and counts, no time.
"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from chipbench.kinds import glm_step
from chipbench.references import glm_plain as ref
from heat_tpu import telemetry
from heat_tpu.nn import (
    DataParallel, DroplessMoE, Latent, LatentAttention, TransformerBlock, TransformerLM, balance_bias_rule,
    causal_lm_loss, glm_4_7_flash, read_routing,
)
from heat_tpu.nn import transformer

# one dense block, four expert blocks and the prediction module
C = dict(
    hidden_size=48, num_attention_heads=3, q_lora_rank=20, kv_lora_rank=12, qk_nope_head_dim=10, qk_rope_head_dim=6,
    v_head_dim=16, rms_norm_eps=1e-5, rope_theta=1000000, intermediate_size=80, first_k_dense_replace=1,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=3, num_experts_held=4, first_expert_held=4,
    moe_intermediate_size=16, norm_topk_prob=True, routed_scaling_factor=1.8, vocab_size=97, num_hidden_layers=5,
    num_nextn_predict_layers=1, bias_rate=0.001,
)
COEF = {"load_balance": 0.0, "router_z": 0.0, "mtp": 0.3}
OPT = {"lr": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip": 1.0,
       "warmup_steps": 4, "coef": COEF}
SEED, T = 29, 40

# float32 against float32 at "highest": the same sums in another order, each term
# rounded once (6e-8), through six blocks; observed 2e-7..4e-6
F32 = 2e-5


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    yield
    jax.clear_caches()


def tiny(**fields):
    arch = dict(
        num_layers=5, experts_held=(4, 4), vocab_size=97, d_model=48, num_heads=3, latent=Latent(20, 12, 10, 6, 16),
        dense_d_ff=80, d_ff=16, shared_d_ff=16, num_experts=16, experts_per_token=3, max_len=64,
        dtype=jnp.float32, accum_dtype=None, attn_impl="local", block_size=16,
    )
    return glm_4_7_flash(**{**arch, **fields})


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
def test_forward_both_logits_both_losses_and_parts_match_the_reference(weights, tokens, impl):
    params = glm_step.to_system(weights, C)
    model = tiny(attn_impl=impl)
    want, want_mtp, _ = ref.logits_of(weights, tokens, C)
    got, (got_mtp,) = model.apply(params, tokens, mtp=True)
    assert got.shape == got_mtp.shape == (2, T, 97) and want_mtp.shape == (2, T - 1, 97)
    assert rel(got, want) < F32
    # the rolled form's first T - 1 positions are the reference's T - 1; its last is fed the first token and is no prediction
    assert rel(got_mtp[:, :T - 1], want_mtp) < F32
    assert rel(model.apply(params, tokens), want) < F32  # without ``mtp`` the trunk alone, the module not run
    loss, aux = causal_lm_loss(model)(params, tokens)
    want_loss, parts = ref.loss_parts(weights, tokens, C, COEF)
    assert rel(loss, want_loss) < F32
    for name in ("ce", "ce_mtp"):
        assert rel(aux[name], parts[name]) < F32, name
    for name in ("load_balance", "router_z"):  # means over the layers' tokens: the module's layer has T of them here, T - 1 there
        assert rel(aux[name], parts[name]) < 2e-3, name
    assert rel(loss, aux["ce"] + 0.3 * aux["ce_mtp"]) < 1e-6 and float(aux["ce_mtp"]) > 1.0
    assert aux["expert_counts"].shape == (5, 16)  # four expert layers of the trunk and the module's; the dense block has no router
    np.testing.assert_array_equal(aux["expert_counts"][:4], parts["expert_counts"][:4])
    # the module's layer routes T positions a sequence where the reference routes the T - 1 that carry a prediction
    extra = np.asarray(aux["expert_counts"][4] - parts["expert_counts"][4])
    assert extra.min() >= 0 and extra.sum() == 2 * 3
    assert int(aux["assignments_due"]) == int(aux["assignments_computed"]) == int(aux["expert_counts"][:, 4:8].sum())
    assert aux["assignments_routed"] == 5 * 2 * T * 3
    assert rel(aux["route_bias_max_abs"], jnp.max(jnp.abs(weights["bias"]))) < 1e-7


@highest
@pytest.mark.parametrize("impl", ["local", "flash"])
def test_gradients_of_every_parameter_group(weights, tokens, impl):
    loss_fn = causal_lm_loss(tiny(attn_impl=impl))
    grads = jax.grad(lambda p: loss_fn(p, tokens)[0])(glm_step.to_system(weights, C))
    want = jax.grad(lambda p: ref.loss_parts(p, tokens, C, COEF)[0])(weights)
    got = glm_step.from_system(grads)
    for (path, g), w in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(want)):
        # back through six blocks: observed up to 3e-5 (a latent norm's gain)
        assert g.shape == w.shape and rel(g, w) < 1e-4, jax.tree_util.keystr(path)
    assert not np.any(np.asarray(got["bias"]))  # no gradient reaches the bias
    g_norms, w_norms = ref.group_norms(got), ref.group_norms(want)
    assert set(g_norms) == set(ref.GROUPS)
    for group in ref.GROUPS:
        assert float(w_norms[group]) > 0 and rel(g_norms[group], w_norms[group]) < 1e-4, group
    # the head's and the table's gradients are the sums of their two uses: with the module's loss left out both are others
    alone = jax.grad(lambda p: causal_lm_loss(tiny(attn_impl=impl), mtp_coef=0.0)(p, tokens)[0])(glm_step.to_system(weights, C))
    alone = glm_step.from_system(alone)
    assert rel(alone["head"], want["head"]) > 0.05 and rel(alone["embed"], want["embed"]) > 0.05
    assert not np.any(np.asarray(alone["w_eh"])) and not np.any(np.asarray(alone["layers"][5]["wq_a"]))


@highest
def test_the_references_written_out_backward_pass_is_autodiff(weights, tokens):
    """``glm_plain._gradients`` (a block a program; the head's, the table's and
    the last stream's gradients added up by hand over the trunk and the module)
    against ``jax.grad`` of ``glm_plain.loss_parts``."""
    loss, parts, grads = ref._gradients(weights, tokens, C, COEF, "float32", ref._free_choice(C, tokens))
    (want_loss, want_parts), want = jax.value_and_grad(ref.loss_parts, has_aux=True)(weights, tokens, C, COEF)
    assert rel(loss, want_loss) < 1e-6 and rel(parts["ce_mtp"], want_parts["ce_mtp"]) < 1e-6
    np.testing.assert_array_equal(parts["chosen"], want_parts["chosen"])
    for (path, g), w in zip(jax.tree.leaves_with_path(grads), jax.tree.leaves(want)):
        assert rel(g, w) < 1e-5 or not np.any(np.asarray(w)), jax.tree_util.keystr(path)
    trunk, module, _ = ref.logits_of(weights, tokens, C, last=8)
    assert rel(parts["last_logits"][:, -8:], trunk) < 1e-6 and rel(parts["mtp_logits"][:, -8:], module) < 1e-6
    # each control is another model
    sound = jnp.concatenate([a.reshape(-1) for a in ref.logits_of(weights, tokens, C)[:2]])
    for wrong in ({"rope_all": True}, {"own_rope_key": True}, {"no_kv_norm": True}, {"routed_scaling_factor": 1.0},
                  {"mtp_shift": 0}, {"mtp_shift": 2}):
        other = jnp.concatenate([a.reshape(-1) for a in ref.logits_of(weights, tokens, {**C, **wrong})[:2]])
        assert ref.rms_gap(other, sound) > 0.02, wrong


def test_mixed_precision_is_near_the_reference_and_the_control_is_farther(tokens):
    """bfloat16 operands with float32 accumulation, as the cell runs, at the
    cell's initialisation: near the float32 reference held to the same routing;
    the reference with bfloat16 everywhere is farther from it than the program."""
    weights = ref.init_params(SEED, C, 0.02, 0.02 / 94**0.5)
    params = glm_step.to_system(weights, C)
    model = tiny(dtype=jnp.bfloat16, accum_dtype=jnp.float32)
    (got, (got_mtp,)), sown = model.apply(params, tokens, mtp=True, mutable=["aux"])
    chosen = jnp.stack([sown["aux"][f"block{i}"]["moe"]["moe"][0]["chosen"] for i in model.expert_layers()])
    with jax.default_matmul_precision("highest"):
        _, parts = ref.loss_parts(weights, tokens, C, COEF)
        want, want_mtp, _ = ref.logits_of(weights, tokens, C, forced=chosen)
        control, control_mtp, _ = ref.logits_of(weights, tokens, C, "bf16", forced=chosen)
    assert got.dtype == got_mtp.dtype == jnp.float32
    # bfloat16 operands (2^-9 each) through five blocks, and six: observed 2.2e-3 and 3.3e-3; the control 4.5e-3 and 5.6e-3
    assert ref.rms_gap(got, want) < 3.2e-3 < ref.rms_gap(control, want)
    assert ref.rms_gap(got_mtp[:, :T - 1], want_mtp) < 4.4e-3 < ref.rms_gap(control_mtp, want_mtp)
    for i in range(5):  # nearly every choice of the mixed model is one the float32 reference could have made
        assert ref.routing_disagreement(np.asarray(chosen[i]), np.asarray(parts["probs"][i]), 3, 0.01) <= 0.02


# -- (b) steps ----------------------------------------------------------------------------


@highest
def test_two_steps_with_the_bias_rule_match_the_references_adamw(weights, tokens):
    comm = ht.MeshCommunication(devices=jax.devices()[:1])
    model = tiny(comm=comm, remat=True)
    opt = glm_step.optimizer(OPT)
    dp = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True)
    step = dp.make_train_step(causal_lm_loss(model), has_aux=True, state_rule=balance_bias_rule(C["bias_rate"]))
    params = jax.tree.map(jnp.copy, glm_step.to_system(weights, C))
    state = opt.init({"params": params["params"]})
    want, want_state = jax.tree.map(jnp.copy, weights), ref.adamw_init(weights)
    moved = np.zeros((5, 16), np.float32)
    for i in range(2):
        batch = jnp.asarray(ref.batch(SEED, i, 2, T, ref.zipf_cdf(97)))
        params, state, loss, aux = step(params, state, batch)
        loss, aux = read_routing(loss, aux)
        want, want_state, want_loss, parts = ref.train_step(want, want_state, batch, C, OPT)
        assert rel(loss, want_loss) < F32 and rel(aux["ce_mtp"], parts["ce_mtp"]) < F32
        np.testing.assert_array_equal(aux["expert_counts"][:4], parts["expert_counts"][:4])
        counts = np.asarray(aux["expert_counts"], np.float32)
        moved += C["bias_rate"] * np.sign(counts.mean(-1, keepdims=True) - counts)
    got = glm_step.from_system(params)
    # the rule moved every bias of all five layers by the rate, twice, from the step's own counts
    np.testing.assert_allclose(got["bias"], np.asarray(weights["bias"]) + moved, rtol=0, atol=1e-7)
    # the trunk's four layers count alike on both sides; the module's counts differ by its last positions' choices
    np.testing.assert_allclose(got["bias"][:4], want["bias"][:4], rtol=0, atol=1e-9)
    for (path, g), w, before in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(want), jax.tree.leaves(weights)):
        if jax.tree_util.keystr(path) == "['bias']":
            continue
        # Adam's m / sqrt(v) turns a gradient entry's rounding into a share of the update: the gap is held
        # against the update's own size (observed under 2e-3 of it)
        gone = float(jnp.sqrt(jnp.sum((w - before) ** 2)))
        assert float(jnp.sqrt(jnp.sum((g - w) ** 2))) <= 1e-2 * gone + 1e-9, jax.tree_util.keystr(path)


# -- (c) the latent mixer alone ------------------------------------------------------------


def _mixer(impl="local", **kw):
    return LatentAttention(3, Latent(20, 12, 10, 6, 16), impl, block_size=16, norm_eps=1e-5, rope_theta=1e6, **kw)


def mixer_params(seed, c):
    """A mixer's seeded leaves: matrices normal(0, 1 / sqrt(rows)), so that a
    head's scores have a deviation near 1 (at the model's own 0.02 a query
    spreads evenly over its keys and no rotary, no shared key and no norm moves
    the output past rounding); gains 1."""
    names = {*glm_step.LATENT.values(), *glm_step.LATENT_NORMS.values()}
    shapes = {n: s for n, s in ref.param_shapes(c)["layers"][0].items() if n in names}
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 5151)
    return {
        n: jnp.ones(s, jnp.float32) if n.startswith("g_") else ref._normal(jax.random.fold_in(key, i), s, float(s[0]) ** -0.5)
        for i, (n, s) in enumerate(sorted(shapes.items()))
    }


def mixer_and_gradients(c, lp, u, weights):
    """The reference's written-out mixer on an already normed input ``u`` and
    the gradients of ``sum(out * weights)`` by ``u`` and by its seven leaves."""

    def f(lp, u):
        out = ref.latent_attention(ref._Numerics("float32"), c, lp, u)
        return jnp.sum(out * weights), out

    (_, out), (d_lp, d_u) = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(lp, u)
    return {"out": out, "du": d_u, **{"d" + n: g for n, g in d_lp.items()}}


@highest
@pytest.mark.parametrize("impl", ["local", "flash"])
def test_the_latent_mixer_is_the_written_out_form(impl):
    leaves = mixer_params(SEED, C)
    leaves = {n: a + 0.2 * jnp.cos(jnp.arange(a.size, dtype=jnp.float32)) if n.startswith("g_") else a for n, a in leaves.items()}
    key = jax.random.PRNGKey(2)
    u, weights = (jax.random.normal(k, (2, T, 48), jnp.float32) for k in jax.random.split(key))
    want = mixer_and_gradients(C, leaves, u, weights)

    def f(p, u):
        out = _mixer(impl).apply({"params": p}, u)
        return jnp.sum(out * weights), out

    (_, out), (d_p, d_u) = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(glm_step.mixer_to_system(leaves, C), u)
    got = {"out": out, "du": d_u, **{"d" + n: g for n, g in glm_step.mixer_from_system(d_p).items()}}
    assert set(got) == set(want) and len(want) == 9
    for name in want:
        assert ref.rms_gap(got[name], want[name]) < F32, name
    # and each control is another mixer
    for wrong in ("rope_all", "own_rope_key", "no_kv_norm"):
        other = mixer_and_gradients({**C, wrong: True}, leaves, u, weights)
        assert ref.rms_gap(other["out"], want["out"]) > 0.05, wrong


@highest
def test_the_one_rotary_key_takes_the_sum_of_the_heads_gradients():
    """``k_r`` has no head axis: every head's key ends in it, so its gradient
    is the sum over the heads of what each head's own copy would get."""
    b, t, heads, nope, rope = 1, 12, 3, 10, 6
    key = jax.random.split(jax.random.PRNGKey(4), 5)
    q = jax.random.normal(key[0], (b, t, heads, nope + rope))
    k_nope = jax.random.normal(key[1], (b, t, heads, nope))
    v = jax.random.normal(key[2], (b, t, heads, nope + rope))
    k_r = jax.random.normal(key[3], (b, t, rope))
    w = jax.random.normal(key[4], (b, t, heads, nope + rope))
    num = ref._Numerics("float32")

    def shared(k_r):
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r[:, :, None, :], (b, t, heads, rope))], axis=-1)
        return jnp.sum(ref.masked_attention(num, q, k, v, np.int32(t)) * w)

    def own(k_rs):  # a key of its own for each head
        return jnp.sum(ref.masked_attention(num, q, jnp.concatenate([k_nope, k_rs], axis=-1), v, np.int32(t)) * w)

    each = jax.grad(own)(jnp.broadcast_to(k_r[:, :, None, :], (b, t, heads, rope)))
    assert rel(jax.grad(shared)(k_r), each.sum(axis=2)) < 1e-6
    # and the module's own parameters see it so: the rope columns of W_kva take all heads' cotangents
    leaves = mixer_params(SEED, C)
    u, weights = (jax.random.normal(k, (1, t, 48), jnp.float32) for k in key[:2])
    d_kva = mixer_and_gradients(C, leaves, u, weights)["dwkv_a"]
    assert float(jnp.abs(d_kva[:, 12:]).max()) > 0 and d_kva.shape == (48, 12 + 6)


def test_a_latent_head_must_be_as_wide_as_its_value():
    with pytest.raises(ValueError, match="as wide as its value"):
        LatentAttention(2, Latent(8, 8, 4, 4, 16)).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))


# -- (d) the prediction module ---------------------------------------------------------------


@highest
def test_the_leak_probe_moves_nothing_before_the_token(weights, tokens):
    """Moving the token at ``j`` must not move the trunk's logits before ``j``
    nor the module's before ``j - 1``; the trunk's at ``j`` and the module's at
    ``j - 1`` must move. The reference fed the embedding two ahead leaks into
    ``j - 2``; fed the token itself its position ``j - 1`` stands still."""
    params = glm_step.to_system(weights, C)
    model = tiny()
    j = 25
    moved = tokens.at[:, j].set((tokens[:, j] + 5) % 97)
    a, (a_mtp,) = model.apply(params, tokens, mtp=True)
    b, (b_mtp,) = model.apply(params, moved, mtp=True)
    assert float(jnp.abs(a[:, :j] - b[:, :j]).max()) == 0 and float(jnp.abs(a[:, j] - b[:, j]).max()) > 1e-3
    assert float(jnp.abs(a_mtp[:, :j - 1] - b_mtp[:, :j - 1]).max()) == 0
    assert float(jnp.abs(a_mtp[:, j - 1] - b_mtp[:, j - 1]).max()) > 1e-3
    # the kind's number, on windows of the last 16 positions cut as the kind cuts them
    last, cut = 16, j - (T - 16)
    window = lambda x, y: np.stack([np.asarray(x[:1, -last:]), np.asarray(y[:1, -last - 1:-1])])  # noqa: E731
    assert glm_step._leak(window(a, a_mtp), window(b, b_mtp), cut) == 0.0
    for shift, reads in ((2, lambda gap: gap > 0.01 and gap != 1.0), (0, lambda gap: gap == 1.0)):
        wrong = {**C, "mtp_shift": shift}
        x, x_mtp, _ = ref.logits_of(weights, tokens, wrong, last=last)
        y, y_mtp, _ = ref.logits_of(weights, moved, wrong, last=last)
        assert reads(glm_step._leak(np.stack([x[:1], x_mtp[:1]]), np.stack([y[:1], y_mtp[:1]]), cut)), shift


def test_modules_beyond_the_first_stand_further_ahead(tokens):
    """Two modules: the second merges the first's stream with the embedding two
    ahead, its block is block ``num_layers + 1``, and its loss is against the
    token three ahead (DeepSeek-V3's chain, of which the cell runs one link)."""
    model = tiny(num_layers=2, mtp_modules=2)
    params = {k: v for k, v in model.init(jax.random.PRNGKey(0), tokens).items() if k != "aux"}
    assert {"block2", "block3", "mtp0_eh_proj", "mtp1_eh_proj", "mtp1_ln_f"} <= set(params["params"])
    assert model.expert_layers() == (1, 2, 3) and set(params["route_bias"]) == {"block1", "block2", "block3"}
    _, (first, second) = model.apply(params, tokens, mtp=True)
    moved = tokens.at[:, 20].set((tokens[:, 20] + 1) % 97)
    _, (first_m, second_m) = model.apply(params, moved, mtp=True)
    still = lambda a, b, upto: float(jnp.abs(a[:, :upto] - b[:, :upto]).max()) == 0  # noqa: E731
    assert still(first, first_m, 19) and not still(first, first_m, 20)
    assert still(second, second_m, 18) and not still(second, second_m, 19)
    loss, aux = causal_lm_loss(model)(params, tokens)
    assert aux["expert_counts"].shape == (3, 16) and rel(loss, aux["ce"] + 0.3 * aux["ce_mtp"]) < 1e-6


# -- (e) the shares add up -----------------------------------------------------------------


@highest
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer_at_scale_1_8():
    """16 experts over four ranks of four (the cell: 64 over eight of eight):
    the parts that the four shares give, each from its own four experts' weights
    and every one with the whole shared expert in it, add up, the shared expert
    counted once, to what the uncut reference gives for the whole layer; so do
    the program's shares."""
    whole = {**C, "num_experts_held": 16, "first_expert_held": 0}
    key = jax.random.PRNGKey(3)
    shapes = {"wr": (48, 16), "wg": (16, 48, 16), "wu": (16, 48, 16), "wd": (16, 16, 48),
              "ws_g": (48, 16), "ws_u": (48, 16), "ws_d": (16, 48)}
    lp = {name: 0.3 * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) for i, (name, shape) in enumerate(shapes.items())}
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 9), (16,), jnp.float32)
    h = jax.random.normal(jax.random.fold_in(key, 10), (64, 48), jnp.float32)
    want, counts = ref.experts_layer(whole, lp, bias, h)
    shared = ref._swiglu(ref._Numerics("float32"), h, lp["ws_g"], lp["ws_u"], lp["ws_d"])
    total_ref, total_sys = jnp.zeros_like(want), jnp.zeros_like(want)
    for first in range(0, 16, 4):
        share = {**C, "num_experts_held": 4, "first_expert_held": first}
        mine = {**lp, **{k: lp[k][first:first + 4] for k in ("wg", "wu", "wd")}}
        part, share_counts = ref.experts_layer(share, mine, bias, h)
        np.testing.assert_array_equal(share_counts, counts)  # every share routes over all sixteen
        total_ref = total_ref + part
        layer = DroplessMoE(16, 3, 16, norm_topk=True, score="sigmoid", select_bias=True, route_scale=1.8,
                            shared_d_ff=16, shared_gate=False, experts_held=(first, 4))
        tree = {"params": {"router": lp["wr"], "w_gate": mine["wg"], "w_up": mine["wu"], "w_down": mine["wd"],
                           **{n: {"kernel": lp[w]} for n, w in glm_step.SHARED.items()}},
                "route_bias": {"bias": bias}}
        got, _ = layer.apply(tree, h[None], mutable=["aux"])
        assert rel(got[0], part) < F32, first
        total_sys = total_sys + got[0]
    # four float32 partial sums against one sum of sixteen terms in another order; three of the four shared experts taken off
    assert rel(total_ref - 3 * shared, want) < F32 and rel(total_sys - 3 * shared, want) < F32
    # the scale is in it: at 1.0 the routed part is 1.8 times smaller
    plain, _ = ref.experts_layer({**whole, "routed_scaling_factor": 1.0}, lp, bias, h)
    assert rel(1.8 * (plain - shared), want - shared) < F32 and float(jnp.max(jnp.abs(want - shared))) > 0.01


# -- (f) the accepted models' programs --------------------------------------------------------


def _digest(model, tokens, **loss):
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))
    params = {k: v for k, v in shapes.items() if k != "aux"}
    text = str(jax.make_jaxpr(lambda p: jax.value_and_grad(causal_lm_loss(model, **loss), has_aux=True)(p, tokens))(params))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name, fields", [
    ("olmoe_1b_7b", dict(num_layers=2, d_model=32, num_heads=2, d_ff=16, num_experts=4, experts_per_token=2)),
    ("qwen3_next_80b_a3b", dict(num_layers=4, d_model=32, num_heads=2, num_kv_heads=1, head_dim=16, gdn_key_heads=2,
                                gdn_value_heads=2, gdn_key_dim=8, gdn_value_dim=8, d_ff=16, shared_d_ff=16, num_experts=4,
                                experts_per_token=2)),
    ("trinity_mini", dict(num_layers=4, d_model=32, num_heads=2, num_kv_heads=1, head_dim=16, dense_d_ff=24, d_ff=16,
                          shared_d_ff=16, num_experts=4, experts_per_token=2, windows=(4, 4, 4, None), dense_layers=1)),
    ("lfm2_24b_a2b", dict(num_layers=4, d_model=32, num_heads=2, num_kv_heads=1, head_dim=16, dense_d_ff=24, d_ff=16,
                          num_experts=4, experts_per_token=2)),
])
def test_the_new_fields_defaults_leave_an_accepted_models_program_as_it_is(name, fields):
    """The accepted builders take no new field: with ``latent`` None,
    ``mtp_modules`` 0 and any ``mtp_coef`` their loss and gradients trace to one
    jaxpr, the one that a ``TransformerLM`` without the new fields' code paths
    gives (the scopes ``lm.targets`` and ``lm.head_loss`` side by side as before:
    the text holds them), and nothing of this PR's scopes is in it."""
    tokens = jnp.zeros((2, 16), jnp.int32)
    model = getattr(transformer, name)(vocab_size=64, max_len=32, attn_impl="local", **fields)
    assert model.latent is None and model.mtp_modules == 0
    assert _digest(model, tokens) == _digest(model, tokens, mtp_coef=0.0) == _digest(model.clone(mtp_modules=0, latent=None), tokens)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))
    params = {k: v for k, v in shapes.items() if k != "aux"}
    assert not any(k.startswith("mtp") for k in params["params"])
    text = str(jax.make_jaxpr(lambda p: causal_lm_loss(model)(p, tokens), )(params))
    lowered = jax.jit(lambda p: causal_lm_loss(model)(p, tokens)).lower(params).as_text(debug_info=True)
    assert "mla." not in lowered and "mtp." not in lowered and "ce_mtp" not in text
    assert "lm.head_loss" in lowered and "lm.targets" in lowered and "lm.head_loss/lm.targets" not in lowered


# -- (g) the published sizes, the counters, the scopes ----------------------------------------


def test_the_builder_is_the_published_configuration():
    m = glm_4_7_flash()
    assert (m.num_layers, m.d_model, m.num_heads, m.mtp_modules) == (47, 2048, 20, 1)
    assert m.latent == Latent(q_rank=768, kv_rank=512, nope=192, rope=64, v=256) and m.latent.nope + m.latent.rope == m.latent.v
    assert all(m.mixer_of(i) == "latent" for i in range(48)) and m.windows == (None,)
    assert (m.dense_layers, m.dense_d_ff, m.d_ff, m.num_experts, m.experts_per_token) == (1, 10240, 1536, 64, 4)
    assert m.expert_layers() == tuple(range(1, 48))  # blocks 1..46 and the module's, block 47
    assert (m.router_score, m.router_bias, m.norm_topk, m.norm_topk_eps, m.route_scale) == ("sigmoid", True, True, 1e-20, 1.8)
    assert (m.shared_d_ff, m.shared_gate) == (1536, False)
    assert (m.norm, m.norm_eps, m.rope_theta, m.vocab_size, m.max_len) == ("rmsnorm", 1e-5, 1e6, 154880, 202752)
    assert not m.sandwich_norm and m.embed_scale is None and not m.tie_embeddings and not m.qk_norm
    assert m.init_std == 0.02 and m.out_init_std == pytest.approx(0.02 / 94**0.5)
    assert glm_4_7_flash(mtp_modules=0).expert_layers() == tuple(range(1, 47))


def test_the_cut_holds_the_issues_parameter_count():
    cut = glm_4_7_flash(num_layers=5, experts_held=(0, 8), vocab_size=19360)
    shapes = jax.eval_shape(lambda: cut.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    p = shapes["params"]
    assert count(p["block0"]["attn"]) == 21_759_232 and count(p["block1"]["moe"]) == 131_072 + 75_497_472 + 9_437_184
    assert count(p["block0"]) == 84_677_888 and all(count(p[f"block{i}"]) == 106_829_056 for i in range(1, 6))
    assert count(p["embed"]) + count(p["lm_head"]) + count(p["ln_f"]) == 79_300_608
    module = sum(count(p[n]) for n in ("mtp0_enorm", "mtp0_hnorm", "mtp0_eh_proj", "mtp0_ln_f", "block5"))
    assert module == 115_223_808 and count(p["mtp0_eh_proj"]) == 4096 * 2048
    assert count(p) == 706_518_528  # 11.30 GB at 16 bytes a parameter
    assert count(shapes["route_bias"]) == 5 * 64 and set(shapes["route_bias"]) == {f"block{i}" for i in range(1, 6)}
    assert ref.param_shapes({**C, **dict(
        hidden_size=2048, num_attention_heads=20, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, intermediate_size=10240, n_routed_experts=64, num_experts_held=8,
        moe_intermediate_size=1536, vocab_size=19360,
    )})["layers"][5]["wkv_b"] == (512, 8960)


def test_an_unknown_mixer_is_refused_with_the_four_that_exist():
    assert transformer.MIXERS == ("attention", "deltanet", "shortconv", "latent")
    with pytest.raises(ValueError, match=r"'attention', 'deltanet', 'shortconv', 'latent'"):
        TransformerBlock(4, mixer="hyena").init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))
    with pytest.raises(ValueError, match="mixer must be one of"):
        TransformerLM(11, 8, 2, 1, mixers=("mla",)).init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_counters_and_scopes_say_what_a_trace_built(tokens):
    counters = telemetry.get_registry().counters
    names = ("mla.mixers", "mla.key_rows_built", "lm.mtp.modules", "attn.full.kernel")
    before = {k: counters[k] if k in counters else 0 for k in names}
    model = tiny(attn_impl="flash", block_size=None)
    params = {k: v for k, v in model.init(jax.random.PRNGKey(1), tokens).items() if k != "aux"}
    added = lambda k: counters[k] - before[k]  # noqa: E731
    assert added("mla.mixers") == 6 and added("lm.mtp.modules") == 1  # five blocks and the module's
    assert added("mla.key_rows_built") == 6 * 2 * T * 3 * 16 and added("attn.full.kernel") == 6
    model.apply(params, tokens)  # the trunk alone: the module is not traced
    assert added("mla.mixers") == 11 and added("lm.mtp.modules") == 1
    text = jax.jit(lambda p: jax.grad(lambda p: causal_lm_loss(model)(p, tokens)[0])(p)).lower(params).as_text(debug_info=True)
    for scope in ("mla.down", "mla.up", "mla.assemble", "attn.full", "mtp.merge", "mtp.block", "mtp.head_loss", "lm.head_loss"):
        assert scope in text, scope
