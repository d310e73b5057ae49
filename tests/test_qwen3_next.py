"""Qwen3-Next through ``ht.nn`` against its plain reference
(``chipbench/references/qwen3_next_plain.py``), on the CPU at tiny widths with
seeded weights: (a) the chunked delta rule against the recurrence, (b) gated
grouped-query attention, (c) the shares of an expert layer add up to the whole
layer, (d) the whole model in float32 and in mixed precision, with both
controls failing, (e) two steps of ``make_train_step`` against the reference's
AdamW, (f) the layer pattern. A CPU run gives results and counts, no time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from chipbench.kinds import qnext_step
from chipbench.references import qwen3_next_plain as ref
from heat_tpu.nn import (
    DataParallel, DroplessMoE, GatedDeltaNet, MultiHeadAttention, TransformerLM, causal_lm_loss,
    gated_delta_rule, qwen3_next_80b_a3b, read_routing,
)
from heat_tpu.nn.transformer import ZeroCentredRMSNorm

C = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25,
    rope_theta=1e7, rms_norm_eps=1e-6, full_attention_interval=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel_dim=4,
    num_experts=16, num_experts_per_tok=3, num_experts_held=4, first_expert_held=4,
    moe_intermediate_size=16, shared_expert_intermediate_size=16, norm_topk_prob=True, vocab_size=97,
    num_hidden_layers=4,
)
COEF = {"load_balance": 0.001, "router_z": 0.0}
OPT = {"lr": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip": 1.0,
       "warmup_steps": 4, "coef": COEF}
SEED, T = 13, 48  # 48 positions: no multiple of the 32 the tiny model's delta rule takes a chunk

# float32 against float32 at "highest": the same sums in another order, each
# term rounded once (6e-8), through four blocks; observed 2e-7..4e-6
F32 = 2e-5
# the gradients of what makes the decay (A_log, dt_bias, W_ba): the chunked form
# sums a chunk's log-decays before it exponentiates, and their gradient comes
# back through that running sum, up to 32 terms of both signs that cancel; the
# recurrence multiplies a position at a time. Observed 3e-5..4e-4 at these
# weights (0.15: decays from 1 to e^-10 a position); a wrong term reads 0.1 and up
F32_DECAY = 2e-3
DECAY = ("a_log", "dt_bias", "w_ba")


def tiny(**fields):
    arch = dict(
        num_layers=4, experts_held=(4, 4), vocab_size=97, d_model=32, num_heads=4, num_kv_heads=2,
        head_dim=16, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8, d_ff=16,
        num_experts=16, experts_per_token=3, shared_d_ff=16, max_len=64, dtype=jnp.float32,
        accum_dtype=None,
    )
    return qwen3_next_80b_a3b(**{**arch, **fields})


@pytest.fixture(scope="module")
def weights():
    # norm gains and the recurrence's parameters away from their initial 0 and 1,
    # so that a gain applied in the wrong form shows
    w = ref.init_params(SEED, C, 0.15, 0.1)
    key = jax.random.PRNGKey(SEED)
    leaves, tree = jax.tree.flatten(w)
    leaves = [
        a + 0.2 * jax.random.normal(jax.random.fold_in(key, i), a.shape, jnp.float32) if a.ndim == 1 else a
        for i, a in enumerate(leaves)
    ]
    return jax.tree.unflatten(tree, leaves)


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


def grads_close(got, want, tol, what=""):
    for (path, g), w in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        limit = F32_DECAY if any(f"'{d}'" in name for d in DECAY) else tol
        assert g.shape == w.shape and rel(g, w) < limit, what + name


# -- (a) the chunked delta rule against the recurrence ----------------------------------


def rule_inputs(t, seed=0, b=2, h=3, dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = functools.partial(jax.random.normal, dtype=jnp.float32)
    q = normal(ks[0], (b, t, h, dk))
    k = normal(ks[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / 4
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = normal(ks[2], (b, t, h, dv))
    g = -0.5 * jax.nn.softplus(normal(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


@highest
@pytest.mark.parametrize("t", [128, 100, 7])  # a multiple of the chunk, not one, shorter than one
def test_the_chunked_delta_rule_is_the_recurrence(t):
    """Outputs and the gradient of every input. The chunked form solves a
    chunk's writes at once and carries the state a chunk at a time; the
    reference's recurrence goes a position at a time. Float32 both."""
    args = rule_inputs(t)
    recurrence = lambda q, k, v, g, beta: ref.delta_rule(q, k, v, jnp.exp(g), beta)  # noqa: E731
    got, want = gated_delta_rule(*args, chunk=32), recurrence(*args)
    assert got.shape == want.shape == (2, t, 3, 8) and got.dtype == jnp.float32
    assert rel(got, want) < F32
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(3 * f(*a)))  # noqa: E731
    g_got = jax.grad(loss(lambda *a: gated_delta_rule(*a, chunk=32)), argnums=range(5))(*args)
    g_want = jax.grad(loss(recurrence), argnums=range(5))(*args)
    grads_close(g_got, g_want, F32)


@highest
def test_the_chunk_length_changes_nothing():
    args = rule_inputs(96, seed=1)
    a, b = gated_delta_rule(*args, chunk=16), gated_delta_rule(*args, chunk=64)
    assert rel(a, b) < F32


def test_the_backward_pass_keeps_the_state_a_chunk_not_a_position():
    """The differentiated rule's largest array of states is (chunks, B, H, Dk,
    Dv): T / chunk states, never T of them."""
    args = rule_inputs(256)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a, chunk=32)), argnums=range(5))).lower(
        *args
    ).as_text()
    assert "tensor<8x2x3x16x8xf32>" in text  # 256 / 32 chunk states
    assert "tensor<256x2x3x16x8xf32>" not in text and "tensor<2x256x3x16x8xf32>" not in text


@highest
@pytest.mark.parametrize("t", [64, 48])
def test_the_deltanet_mixer_against_the_reference(weights, t):
    """The module (projections, convolution, gates, rule, gated norm): output,
    and the gradient of the input and of every parameter."""
    lp = weights["layers"][0]
    params = {"params": qnext_step.to_system(weights, C)["params"]["block0"]["gdn"]}
    mixer = GatedDeltaNet(2, 4, 8, 8, 4, 1e-6, chunk=32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, t, 32), jnp.float32)
    num = ref._Numerics("float32")
    assert rel(mixer.apply(params, x), ref._deltanet(num, C, lp, x)) < F32
    loss = lambda y: jnp.sum(jnp.sin(y))  # noqa: E731
    got = jax.grad(lambda p, x: loss(mixer.apply(p, x)), argnums=(0, 1))(params, x)
    want = jax.grad(lambda lp, x: loss(ref._deltanet(num, C, lp, x)), argnums=(0, 1))(lp, x)
    g = got[0]["params"]
    named = {
        "w_qkvz": g["in_qkvz"], "w_ba": g["in_ba"], "conv": g["conv"],
        "a_log": g["A_log"], "dt_bias": g["dt_bias"], "g_o": g["norm"], "w_out": g["out"],
    }
    for name, value in named.items():  # 5e-5: the widest sums (8 x 48 positions a column of W_qkvz) read 4.2e-5
        assert rel(value, want[0][name]) < (F32_DECAY if name in DECAY else 5e-5), name
    assert rel(got[1], want[1]) < 5e-5


def test_a_bfloat16_state_fails_where_the_chunked_rule_in_its_stated_precision_does_not():
    """The control for the delta rule's arithmetic, in the regime where the
    state's precision matters: heads that remember 8 to 2,048 positions. The
    chunked rule with bfloat16 operands in its products (float32 decay, state
    and solve) stays near the float32 recurrence; the recurrence with the state
    stored in bfloat16 and alpha, beta rounded to it does not: an alpha within
    2^-9 of 1 rounds to 1 and the head stops forgetting."""
    q, k, v, _, beta = rule_inputs(1024, seed=3, b=1, h=8)
    g = jnp.broadcast_to(-1.0 / (2.0 ** jnp.arange(3, 11, dtype=jnp.float32)), (1, 1024, 8))
    with jax.default_matmul_precision("highest"):
        want = ref.delta_rule(q, k, v, jnp.exp(g), beta)
        control = ref.delta_rule(q, k, v, jnp.exp(g), beta, low_state=True)
    got = gated_delta_rule(q, k, v, g, beta, dtype=jnp.bfloat16)
    assert ref.rms_gap(got, want) < 8e-3 < 2e-2 < ref.rms_gap(control, want)  # 5.4e-3, 2.9e-2


# -- (b) gated attention: 4 query heads on 2 key-value heads, partial rotary -------------


@highest
@pytest.mark.parametrize("impl, bwd", [("flash", "two_pass"), ("flash", "auto"), ("local", "auto")])
def test_gated_attention_against_the_reference(weights, impl, bwd):
    """The flash kernels (in the interpreter here) read a group's key-value
    head by index and give dk, dv summed over the group; the local path
    repeats K and V. Output and every gradient, float32."""
    lp = weights["layers"][3]
    params = {"params": qnext_step.to_system(weights, C)["params"]["block3"]["attn"]}
    attn = MultiHeadAttention(
        4, impl, flash_bwd_impl=bwd, qk_norm_eps=1e-6, rope_theta=1e7, num_kv_heads=2, head_dim=16,
        qk_norm_over="head", norm="rmsnorm_zero", rotary_fraction=0.25, gate=True,
    )
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, 32), jnp.float32)
    num = ref._Numerics("float32")
    assert rel(attn.apply(params, x), ref._attention(num, C, lp, x)) < F32
    loss = lambda y: jnp.sum(jnp.sin(y))  # noqa: E731
    got = jax.grad(lambda p, x: loss(attn.apply(p, x)), argnums=(0, 1))(params, x)
    want = jax.grad(lambda lp, x: loss(ref._attention(num, C, lp, x)), argnums=(0, 1))(lp, x)
    g = got[0]["params"]
    named = {
        "wq": g["query"]["kernel"].reshape(32, -1), "wk": g["key"]["kernel"].reshape(32, -1),
        "wv": g["value"]["kernel"].reshape(32, -1), "wo": g["out"]["kernel"].reshape(-1, 32),
        "g_q": g["q_norm"]["scale"], "g_k": g["k_norm"]["scale"],
    }
    for name, value in named.items():
        assert rel(value, want[0][name]) < F32, name
    assert rel(got[1], want[1]) < F32


def test_grouped_heads_are_refused_where_they_do_not_divide():
    from heat_tpu.parallel import flash_attention

    q, kv = jnp.zeros((1, 8, 4, 8)), jnp.zeros((1, 8, 3, 8))
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q, kv, kv)


def test_the_zero_centred_norm_starts_as_the_plain_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16), jnp.float32)
    norm = ZeroCentredRMSNorm(1e-6)
    params = norm.init(jax.random.PRNGKey(1), x)
    assert float(jnp.abs(params["params"]["scale"]).max()) == 0.0
    want = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    assert rel(norm.apply(params, x), want) < 1e-6
    half = {"params": {"scale": jnp.full((16,), 0.5)}}
    assert rel(norm.apply(half, x), 1.5 * want) < 1e-6


# -- (c) the shares of an expert layer add up ------------------------------------------------


def expert_layer(first, held, shared=16):
    return DroplessMoE(16, 3, 16, norm_topk=True, shared_d_ff=shared, experts_held=(first, held))


def share_of(moe_params, first, held, shared=True):
    p = {k: v for k, v in moe_params.items() if shared or not k.startswith("shared")}
    return {"params": {**p, **{n: moe_params[n][first:first + held] for n in ("w_gate", "w_up", "w_down")}}}


@pytest.fixture(scope="module")
def uncut():
    """An expert layer with all 16 experts, as the reference's and as the
    system's parameters."""
    c = {**C, "num_experts_held": 16, "first_expert_held": 0, "num_hidden_layers": 1, "full_attention_interval": 1}
    w = ref.init_params(SEED + 1, c, 0.3, 0.3)
    return c, w["layers"][0], qnext_step.to_system(w, c)["params"]["block0"]["moe"]


@highest
def test_the_shares_add_up_to_the_uncut_layer(uncut):
    """16 experts over 4 shares: the four partial results, with the shared
    expert counted once, are the uncut reference layer, and every share
    normalises its top-k weights as the uncut layer does, over all k chosen."""
    c, lp, moe_params = uncut
    x = jax.random.normal(jax.random.PRNGKey(4), (2, T, 32), jnp.float32)
    num = ref._Numerics("float32")
    want, _, _, counts, chosen, _, w = ref._experts(num, c, lp, x.reshape(-1, 32))
    total, held = 0.0, []
    for s in range(4):
        y, sown = expert_layer(4 * s, 4, shared=0).apply(share_of(moe_params, 4 * s, 4, False), x, mutable=["aux"])
        a = sown["aux"]["moe"][0]
        np.testing.assert_array_equal(a["chosen"], chosen)
        assert rel(a["weights"], w) < F32 and rel(jnp.sum(a["weights"], -1), jnp.ones(2 * T)) < 1e-6
        assert int(a["held"]) == int(a["computed"]) == int(counts[4 * s:4 * s + 4].sum())
        np.testing.assert_array_equal(a["expert_counts"], counts)  # the auxiliary terms see all 16
        total, held = total + y, held + [int(a["held"])]
    assert sum(held) == 2 * T * 3 and min(held) > 0
    with_shared = expert_layer(0, 4).apply(share_of(moe_params, 0, 4), x)
    without = expert_layer(0, 4, shared=0).apply(share_of(moe_params, 0, 4, False), x)
    assert rel(total + (with_shared - without), want.reshape(2, T, 32)) < F32
    # the whole layer held is the whole layer
    assert rel(expert_layer(0, 16).apply(share_of(moe_params, 0, 16), x), want.reshape(2, T, 32)) < F32


@highest
@pytest.mark.parametrize("routing", ["seeded", "every_token_takes_the_held_experts"])
def test_a_share_against_the_reference_given_the_same_share(uncut, routing):
    """Output, auxiliary terms and every gradient of one share, the reference
    given the same share. With a router that sends every token to the held
    experts the rows pass the first window's bound (2 x an even share: 72 of
    288 here) and the further windows run: nothing is dropped there either."""
    c, lp, moe_params = uncut
    c = {**c, "num_experts_held": 4, "first_expert_held": 8}
    lp = {**lp, **{n: lp[n][8:12] for n in ("wg", "wu", "wd")}}
    if routing != "seeded":
        lp = {**lp, "wr": lp["wr"].at[0, 8:11].add(40.0)}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, 32), jnp.float32).at[..., 0].set(1.0)
    layer = expert_layer(8, 4)
    params = {"params": {**share_of(moe_params, 8, 4)["params"], "router": lp["wr"]}}
    num = ref._Numerics("float32")

    def theirs(lp, x):
        y, lb, z, counts, *_ = ref._experts(num, c, lp, x.reshape(-1, 32))
        return jnp.sum(jnp.sin(y)) + lb + z, counts

    def ours(p, x):
        y, sown = layer.apply(p, x, mutable=["aux"])
        a = sown["aux"]["moe"][0]
        return jnp.sum(jnp.sin(y)) + a["load_balance"] + a["router_z"], a

    (got, a), g_got = jax.value_and_grad(ours, argnums=(0, 1), has_aux=True)(params, x)
    (want, counts), g_want = jax.value_and_grad(theirs, argnums=(0, 1), has_aux=True)(lp, x)
    assert rel(got, want) < F32
    assert int(a["held"]) == int(a["computed"]) == int(counts[8:12].sum())
    if routing != "seeded":
        assert int(a["held"]) == 2 * T * 3 > 2 * 72  # every assignment, three windows' worth
    g = g_got[0]["params"]
    named = {
        "wr": g["router"], "wg": g["w_gate"], "wu": g["w_up"], "wd": g["w_down"],
        "ws_g": g["shared_gate"]["kernel"], "ws_u": g["shared_up"]["kernel"],
        "ws_d": g["shared_down"]["kernel"], "ws_r": g["shared_router"],
    }
    for name, value in named.items():
        assert rel(value, g_want[0][name]) < F32, name
    assert rel(g_got[1], g_want[1]) < F32


def test_the_work_around_the_experts_goes_with_the_held_rows():
    """No array of tokens x top-k rows of hidden features in the share's
    program: the gathers, the grouped products and the sum back into the
    tokens are a window of 2 x an even share long."""
    x = jnp.zeros((2, 512, 32))
    layer = expert_layer(4, 4)
    params = {"params": jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]}
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(layer.apply(p, x)))).lower(params, x).as_text()
    assert "tensor<1536x32xf32>" in text  # the window: 2 x (1024 x 3 / 4)
    assert "tensor<3072x32xf32>" not in text and "tensor<1024x3x32xf32>" not in text
    whole = DroplessMoE(16, 3, 16)
    params = {"params": jax.eval_shape(whole.init, jax.random.PRNGKey(0), x)["params"]}
    text = jax.jit(whole.apply).lower(params, x).as_text()
    assert "tensor<3072x32xf32>" in text  # the layer that holds every expert sorts them all


def test_experts_held_outside_the_layer_are_refused():
    with pytest.raises(ValueError, match="outside"):
        expert_layer(14, 4).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 32)))


# -- (d) the whole model ---------------------------------------------------------------------


@highest
def test_forward_logits_loss_and_parts_match_the_reference(weights, tokens):
    params = qnext_step.to_system(weights, C)
    model = tiny()
    want, _ = ref.logits_of(weights, tokens, C)
    got = model.apply(params, tokens)
    assert got.shape == (2, T, 97) and rel(got, want) < F32
    loss, aux = causal_lm_loss(model, load_balance_coef=0.001)(params, tokens)
    want_loss, parts = ref.loss_parts(weights, tokens, C, COEF)
    assert rel(loss, want_loss) < F32
    for name in ("ce", "load_balance", "router_z"):
        assert rel(aux[name], parts[name]) < F32, name
    np.testing.assert_array_equal(aux["expert_counts"], parts["expert_counts"])
    held = int(parts["expert_counts"][:, 4:8].sum())
    assert int(aux["assignments_due"]) == int(aux["assignments_computed"]) == held
    assert aux["assignments_routed"] == 4 * 2 * T * 3


@highest
def test_gradients_of_every_parameter_group(weights, tokens):
    loss_fn = causal_lm_loss(tiny(), load_balance_coef=0.001)
    grads = jax.grad(lambda p: loss_fn(p, tokens)[0])(qnext_step.to_system(weights, C))
    want = jax.grad(lambda p: ref.loss_parts(p, tokens, C, COEF)[0])(weights)
    got = qnext_step.from_system(grads)
    grads_close(got, want, 1e-4)  # back through four blocks: observed up to 5.5e-5 (a gated norm's gain)
    g_norms, w_norms = ref.group_norms(got), ref.group_norms(want)
    assert set(g_norms) == set(ref.GROUPS)
    for group in ref.GROUPS:
        assert float(w_norms[group]) > 0 and rel(g_norms[group], w_norms[group]) < F32, group


def test_to_system_and_back_is_the_identity(weights):
    back = qnext_step.from_system(qnext_step.to_system(weights, C))
    for (path, a), b in zip(jax.tree.leaves_with_path(back), jax.tree.leaves(weights)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


# bfloat16 operands, float32 accumulation, against the float32 reference at
# these widths (sums of 8 to 97 products of operands rounded to 2^-9) and the
# cell's own initialisation (0.02; 0.02 / sqrt(2 x 4 layers) into the stream):
# the model reads 2.6e-3 (rms) and 2.7e-3 (largest), the control 4.4e-3 and 6.7e-3
MIXED_RMS, MIXED_MAX = 3.4e-3, 4.5e-3


def test_mixed_precision_stays_inside_the_limits_and_the_control_does_not(tokens):
    """The control is the reference a precision below in every product, norm
    and softmax (``bf16``). The control for the delta rule alone
    (``bf16_state``) moves the logits less than the model's own bfloat16
    operands do at an initialisation whose decays forget within a position or
    two: it is held to its own comparison (the rule's test above, and
    ``delta_rule_gap`` on the chip)."""
    weights = ref.init_params(SEED, C, 0.02, 0.02 / 8**0.5)
    params = qnext_step.to_system(weights, C)
    model = tiny(dtype=jnp.bfloat16, accum_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, parts = ref.loss_parts(weights, tokens, C, COEF)
    got, sown = model.apply(params, tokens, mutable=["aux"])
    chosen = jnp.stack([sown["aux"][f"block{i}"]["moe"]["moe"][0]["chosen"] for i in range(4)])
    with jax.default_matmul_precision("highest"):
        want, _ = ref.logits_of(weights, tokens, C, forced=chosen)
        want_loss, _ = ref.loss_parts(weights, tokens, C, COEF, forced=chosen)
        own, _ = ref.logits_of(weights, tokens, C)
        control, _ = ref.logits_of(weights, tokens, C, "bf16", forced=chosen)
        state_control, _ = ref.logits_of(weights, tokens, C, "bf16_state", forced=chosen)
    loss, _ = causal_lm_loss(model, load_balance_coef=0.001)(params, tokens)
    assert got.dtype == jnp.float32
    assert ref.rms_gap(got, want) < MIXED_RMS and rel(got, want) < MIXED_MAX
    assert rel(loss, want_loss) < 3e-4
    assert ref.rms_gap(control, want) > 1.2 * MIXED_RMS and rel(control, want) > 1.2 * MIXED_MAX
    assert 0 < ref.rms_gap(state_control, want) < MIXED_RMS
    # every choice of the mixed model is one the float32 reference could have made
    for i in range(4):
        assert ref.routing_disagreement(np.asarray(chosen[i]), np.asarray(parts["probs"][i]), 3, 0.01) == 0.0
    assert rel(own, want) < 0.2  # forcing moves the reference by a flipped near tie at most


@highest
def test_the_reference_can_be_held_to_a_given_routing(weights, tokens):
    loss, parts = ref.loss_parts(weights, tokens, C, COEF)
    again, same = ref.loss_parts(weights, tokens, C, COEF, forced=parts["chosen"])
    assert float(again) == float(loss)
    np.testing.assert_array_equal(same["expert_counts"], parts["expert_counts"])
    other = (parts["chosen"] + 1) % 16
    moved, diff = ref.loss_parts(weights, tokens, C, COEF, forced=other)
    np.testing.assert_array_equal(diff["chosen"], other)
    assert float(moved) != float(loss)
    assert rel(jnp.sum(diff["weights"], -1), jnp.ones((4, 2 * T))) < 1e-6  # still normalised over the k taken


# -- (e) two steps of make_train_step against the reference's AdamW -----------------------------


@highest
def test_two_adamw_steps_through_make_train_step(weights):
    from heat_tpu import telemetry

    comm = ht.core.communication.MeshCommunication(devices=jax.devices()[:1])
    model = tiny(remat=True, comm=comm)
    opt = qnext_step.optimizer(OPT)
    loss_fn = causal_lm_loss(model, load_balance_coef=0.001)
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        loss_fn, has_aux=True
    )
    params = jax.tree.map(jnp.copy, qnext_step.to_system(weights, C))
    state = opt.init(params)
    rp = jax.tree.map(jnp.copy, weights)
    rs = ref.adamw_init(rp)
    cdf = ref.zipf_cdf(97)
    before = dict(telemetry.get_registry().counters)
    try:
        held = []
        for i in range(2):
            batch = ref.batch(SEED, i, 2, T, cdf)
            params, state, loss, aux = step(params, state, batch)
            loss, aux = read_routing(loss, aux)
            rp, rs, want, parts = ref.train_step(rp, rs, jnp.asarray(batch), C, OPT)
            assert rel(loss, want) < F32, i
            np.testing.assert_array_equal(aux["expert_counts"], parts["expert_counts"])
            held.append(int(parts["expert_counts"][:, 4:8].sum()))
        after = telemetry.get_registry().counters
        counters = {k: after[k] - before.get(k, 0) for k in after if k.startswith("moe.")}
    finally:
        pass
    assert counters["moe.steps"] == 2 and counters["moe.dropped"] == 0
    assert counters["moe.held_assignments"] == counters["moe.assignments"] == sum(held)
    assert abs(counters["moe.held_share"] - sum(held) / (4 * 2 * T * 3)) < 1e-9
    # per leaf, the distance between the two over the reference's own move: Adam's
    # m / sqrt(v) turns an entry whose gradient is within rounding of 0 by up to
    # its whole step, so single entries differ (2.5e-5 of a 3e-4 move) while a
    # leaf does not; a missing decay, clip or moment reads 0.05 and up
    norm = lambda a: float(jnp.sqrt(jnp.sum(a * a)))  # noqa: E731
    for (path, g), w, w0 in zip(
        jax.tree.leaves_with_path(qnext_step.from_system(params)), jax.tree.leaves(rp), jax.tree.leaves(weights)
    ):
        assert norm(g - w) < 2e-2 * norm(w - w0), jax.tree_util.keystr(path)
    moved = float(jnp.abs(rp["layers"][0]["w_qkvz"] - weights["layers"][0]["w_qkvz"]).max())
    assert 2e-4 < moved < 4e-4  # lr 1e-4, then 2e-4: a step that does not step shows


# -- (f) the layer pattern, and the published configuration -------------------------------------


def test_layer_i_is_attention_exactly_where_i_plus_one_divides_by_four():
    model = qwen3_next_80b_a3b()
    assert model.num_layers == 48
    for i in range(48):
        assert (model.mixer_of(i) == "attention") == ((i + 1) % 4 == 0) == ref.is_attention({"full_attention_interval": 4}, i)
    assert sum(model.mixer_of(i) == "deltanet" for i in range(48)) == 36
    shapes = jax.eval_shape(tiny().init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert ["gdn" in shapes[f"block{i}"] for i in range(4)] == [True, True, True, False]
    assert ["attn" in shapes[f"block{i}"] for i in range(4)] == [False, False, False, True]
    with pytest.raises(ValueError, match="mixer"):
        TransformerLM(8, 8, 2, 1, mixers=("conv",)).init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_qwen3_next_is_the_published_configuration():
    """The factory's fields against the catalog's row, and the parameter
    count of the chip's share against the issue's arithmetic."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "chipbench", "configs", "qwen3-next-80b-a3b-train.json")) as f:
        config = json.load(f)
    m = qnext_step.build_model(config, None)
    whole = qwen3_next_80b_a3b()
    assert (whole.vocab_size, whole.num_layers, whole.experts_held) == (151936, 48, None)
    for model in (m, whole):
        assert (model.d_model, model.num_heads, model.num_kv_heads, model.head_dim) == (2048, 16, 2, 256)
        assert (model.gdn_key_heads, model.gdn_value_heads, model.gdn_key_dim, model.gdn_value_dim) == (16, 32, 128, 128)
        assert (model.num_experts, model.experts_per_token, model.d_ff, model.shared_d_ff) == (512, 10, 512, 512)
        assert model.norm_topk and model.attn_gate and model.qk_norm == "head" and model.norm == "rmsnorm_zero"
        assert (model.rotary_fraction, model.rope_theta, model.norm_eps, model.gdn_conv) == (0.25, 1e7, 1e-6, 4)
        assert model.mixers == ("deltanet", "deltanet", "deltanet", "attention")
    assert (m.vocab_size, m.num_layers, m.experts_held, m.remat) == (18992, 4, (0, 32), True)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    moe = count(shapes["block0"]["moe"])
    assert moe == 100_663_296 + 4_196_352  # 32 experts; router, shared expert and its gate
    assert count(shapes["block0"]) == 138_582_208 and count(shapes["block3"]) == 132_127_232
    assert count(shapes["block0"]["gdn"]) == 33_718_464 and count(shapes["block3"]["attn"]) == 27_263_488
    assert count(shapes) == 625_667_136  # 625.67 M: 10.01 GB at 16 bytes
    # the reference's tree is the same tree
    c = {k: config[k] for k in qnext_step.MODEL_KEYS}
    assert sum(int(np.prod(s)) for s in jax.tree.leaves(ref.param_shapes(c), is_leaf=lambda s: isinstance(s, tuple))) == 625_667_136


# -- queries and keys to the flash kernels in one pass (PR 50) ------------------------------


@highest
def test_the_pass_before_the_flash_kernels_is_the_xla_lines_and_holds_the_same_parameters(weights, tokens, monkeypatch):
    """``nn/pallas_qk_prep.py`` in the interpreter: the zero-centred head norm,
    rotary on a quarter of a head, queries beside their gates."""
    from tests.test_pallas_qk_prep import both_forms

    model = tiny(attn_impl="flash")
    (logits, grads, passes), (k_logits, k_grads, _) = both_forms(
        monkeypatch, model, qnext_step.to_system(weights, C), tokens, causal_lm_loss(model, load_balance_coef=0.001)
    )
    assert passes["xla"] >= 2  # the one attention layer of four
    assert rel(k_logits, logits) < F32
    grads_close(k_grads["params"], grads["params"], 1e-4)
