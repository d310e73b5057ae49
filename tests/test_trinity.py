"""Trinity-Mini through ``ht.nn`` against its plain reference
(``chipbench/references/trinity_plain.py``), on the CPU at tiny widths with
seeded weights: (a) the whole model, logits, loss, every group's gradients,
with ``local`` and ``flash`` attention (the Pallas interpreter), in float32 and
in mixed precision with the control failing; (b) three steps of
``make_train_step`` with its rule-updated state against the reference's AdamW
and bias rule; (c) the shares of an expert layer add up to the uncut layer;
(d) rotary on the windowed layers only; (e) the selection bias; (f) the layer
pattern and the published configuration. A CPU run gives results and counts,
no time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from chipbench.kinds import trinity_step
from chipbench.references import trinity_plain as ref
from heat_tpu.nn import (
    DataParallel, DroplessMoE, MultiHeadAttention, TransformerLM, balance_bias_rule, causal_lm_loss,
    read_routing, trinity_mini,
)

C = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=16, rope_theta=10000,
    rms_norm_eps=1e-5, global_attn_every_n_layers=4, sliding_window=12, intermediate_size=48,
    num_dense_layers=2, num_experts=16, num_experts_per_tok=3, num_experts_held=4, first_expert_held=4,
    moe_intermediate_size=16, route_norm=True, route_scale=2.826, vocab_size=97, num_hidden_layers=8,
    bias_rate=0.001,
)
COEF = {"load_balance": 0.0, "router_z": 0.0}
OPT = {"lr": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip": 1.0,
       "warmup_steps": 4, "coef": COEF}
SEED, T = 17, 40  # 40 positions: more than three windows of 12, no multiple of the kernels' blocks of 16

# float32 against float32 at "highest": the same sums in another order, each
# term rounded once (6e-8), through eight blocks of four norms; observed 3e-7..6e-6
F32 = 2e-5


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """Eight-block models with the Pallas interpreter in them are large CPU
    programs; a worker that keeps every one of this file's alive crashed in
    XLA's compile cache after the sixteenth test (a segmentation fault, not a
    Python error). Each test gives its programs back."""
    yield
    jax.clear_caches()


def tiny(**fields):
    arch = dict(
        num_layers=8, experts_held=(4, 4), vocab_size=97, d_model=32, embed_scale=32**0.5, num_heads=4,
        num_kv_heads=2, head_dim=16, windows=(12, 12, 12, None), dense_d_ff=48, d_ff=16, num_experts=16,
        experts_per_token=3, shared_d_ff=16, max_len=64, dtype=jnp.float32, accum_dtype=None,
        attn_impl="local", block_size=16,
    )
    return trinity_mini(**{**arch, **fields})


@pytest.fixture(scope="module")
def weights():
    # norm gains and biases away from their initial 1 and 0, so that a gain applied
    # in the wrong place, or a bias that leaks into the weights, shows
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
    params = trinity_step.to_system(weights, C)
    model = tiny(attn_impl=impl)
    want, _ = ref.logits_of(weights, tokens, C)
    got = model.apply(params, tokens)
    assert got.shape == (2, T, 97) and rel(got, want) < F32
    from heat_tpu import telemetry

    counters = telemetry.get_registry().counters
    assert counters["attn.window.kernel" if impl == "flash" else "attn.window.xla"] >= 6  # six windowed layers
    assert counters["moe.route.sigmoid"] >= 6
    loss, aux = causal_lm_loss(model)(params, tokens)
    want_loss, parts = ref.loss_parts(weights, tokens, C, COEF)
    assert rel(loss, want_loss) < F32
    for name in ("ce", "load_balance", "router_z"):
        assert rel(aux[name], parts[name]) < F32, name
    np.testing.assert_array_equal(aux["expert_counts"], parts["expert_counts"])
    assert aux["expert_counts"].shape == (6, 16)  # the six expert layers; the two dense blocks have no router
    held = int(parts["expert_counts"][:, 4:8].sum())
    assert int(aux["assignments_due"]) == int(aux["assignments_computed"]) == held
    assert aux["assignments_routed"] == 6 * 2 * T * 3
    assert rel(aux["route_bias_max_abs"], jnp.max(jnp.abs(weights["bias"]))) < 1e-7


@highest
@pytest.mark.parametrize("impl", ["local", "flash"])
def test_gradients_of_every_parameter_group(weights, tokens, impl):
    loss_fn = causal_lm_loss(tiny(attn_impl=impl))
    grads = jax.grad(lambda p: loss_fn(p, tokens)[0])(trinity_step.to_system(weights, C))
    want = jax.grad(lambda p: ref.loss_parts(p, tokens, C, COEF)[0])(weights)
    got = trinity_step.from_system(grads)
    for (path, g), w in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(want)):
        # back through eight blocks: observed up to 4e-5 (a head norm's gain)
        assert g.shape == w.shape and rel(g, w) < 1e-4, jax.tree_util.keystr(path)
    assert not np.any(np.asarray(got["bias"]))  # no gradient reaches the bias
    g_norms, w_norms = ref.group_norms(got), ref.group_norms(want)
    assert set(g_norms) == set(ref.GROUPS)
    for group in ref.GROUPS:
        assert float(w_norms[group]) > 0 and rel(g_norms[group], w_norms[group]) < F32, group


def test_to_system_and_back_is_the_identity(weights):
    back = trinity_step.from_system(trinity_step.to_system(weights, C))
    assert set(back) == set(weights)
    for (path, a), b in zip(jax.tree.leaves_with_path(back), jax.tree.leaves(weights)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


# bfloat16 operands, float32 accumulation, against the float32 reference at these
# widths and the cell's own initialisation (0.02; 0.0025 into the stream, which the
# norm on each branch's output brings back to 1: every branch is as large as the
# stream, so the gaps are larger than the other two models'): the model reads
# 6.3e-3 (rms) and 9.4e-3 (largest) with either attention (6.3e-3 to 7.8e-3 and 9.4e-3
# to 1.08e-2 over three seeds), the control 1.07e-2 and 1.9e-2 (sums of 16 to 48 terms:
# a bfloat16 accumulator costs less here than at the cell's 2,048)
MIXED_RMS, MIXED_MAX = 8.5e-3, 1.25e-2


@pytest.mark.parametrize("impl", ["local", "flash"])
def test_mixed_precision_stays_inside_the_limits_and_the_control_does_not(tokens, impl):
    weights = ref.init_params(SEED, C, 0.02, 0.0025)
    params = trinity_step.to_system(weights, C)
    model = tiny(dtype=jnp.bfloat16, accum_dtype=jnp.float32, attn_impl=impl)
    with jax.default_matmul_precision("highest"):
        _, parts = ref.loss_parts(weights, tokens, C, COEF)
    got, sown = model.apply(params, tokens, mutable=["aux"])
    chosen = jnp.stack([sown["aux"][f"block{i}"]["moe"]["moe"][0]["chosen"] for i in model.expert_layers()])
    with jax.default_matmul_precision("highest"):
        want, _ = ref.logits_of(weights, tokens, C, forced=chosen)
        want_loss, _ = ref.loss_parts(weights, tokens, C, COEF, forced=chosen)
        control, _ = ref.logits_of(weights, tokens, C, "bf16", forced=chosen)
    loss, _ = causal_lm_loss(model)(params, tokens)
    assert got.dtype == jnp.float32
    assert ref.rms_gap(got, want) < MIXED_RMS and rel(got, want) < MIXED_MAX
    assert rel(loss, want_loss) < 1e-3
    assert ref.rms_gap(control, want) > 1.2 * MIXED_RMS and rel(control, want) > 1.2 * MIXED_MAX
    # nearly every choice of the mixed model is one the float32 reference could have made
    for i in range(6):
        assert ref.routing_disagreement(np.asarray(chosen[i]), np.asarray(parts["probs"][i]), 3, 0.01) <= 0.02


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
    # still normalised over the k taken, then scaled
    assert rel(jnp.sum(diff["weights"], -1), jnp.full((6, 2 * T), 2.826)) < 1e-6


@highest
def test_the_reference_in_blocks_of_positions_is_the_reference(weights, tokens, monkeypatch):
    """At the cell's 16,384 positions the reference takes what goes a position
    at a time, the attention's queries and the cross-entropy in blocks of 2,048
    (memory only). Here, in blocks of 8 of the 40 positions: the same numbers."""
    coef = {"load_balance": 0.01, "router_z": 0.001}
    whole = jax.value_and_grad(ref.loss_parts, has_aux=True)(weights, tokens, C, coef)
    monkeypatch.setattr(ref, "TOKEN_BLOCK", 8)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    blocked = jax.value_and_grad(ref.loss_parts, has_aux=True)(weights, tokens, C, coef)
    assert rel(blocked[0][0], whole[0][0]) < 1e-6
    for name in ("expert_counts", "chosen"):
        np.testing.assert_array_equal(blocked[0][1][name], whole[0][1][name])
    for name in ("load_balance", "router_z", "probs", "weights"):
        assert rel(blocked[0][1][name], whole[0][1][name]) < 1e-6, name
    for (path, g), w in zip(jax.tree.leaves_with_path(blocked[1]), jax.tree.leaves(whole[1])):
        assert rel(g, w) < 1e-5, jax.tree_util.keystr(path)


@highest
@pytest.mark.parametrize("wrong", [{}, {"full_window": 12}, {"full_rotary": True}])
def test_the_reference_a_block_a_program_is_jax_grad_of_its_loss(weights, tokens, wrong):
    """``ref._gradients`` (what a run on the chip computes: backpropagation
    written out over the blocks, a block a program) against ``jax.grad`` of
    ``ref.loss_parts``, with both auxiliary coefficients on; also with a control
    of the mask or of the positions, which must compile no further block."""
    coef, c = {"load_balance": 0.01, "router_z": 0.001}, {**C, **wrong}
    (loss, parts), grads = jax.value_and_grad(ref.loss_parts, has_aux=True)(weights, tokens, c, coef, "float32", None, 8)
    got_loss, got_parts, got = ref._gradients(weights, tokens, c, coef, "float32", ref._free_choice(c, tokens))
    compiled = ref._block_forward._cache_size(), ref._block_backward._cache_size()
    assert rel(got_loss, loss) < 1e-6 and rel(got_parts["last_logits"][:, -8:], parts["last_logits"]) < F32
    for name in ("expert_counts", "chosen"):
        np.testing.assert_array_equal(got_parts[name], parts[name])
    for (path, g), w in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(grads)):
        assert rel(g, w) < F32, jax.tree_util.keystr(path)
    assert not np.any(np.asarray(got["bias"]))
    ref._gradients(weights, tokens, C, coef, "float32", ref._free_choice(C, tokens))  # a dense block and an expert block
    assert (ref._block_forward._cache_size(), ref._block_backward._cache_size()) == compiled == (2, 2)


def test_the_rolled_bfloat16_accumulator_is_the_written_out_one():
    """``ref._Numerics("bf16").mm`` (a scan over the sum's blocks) against
    ``olmoe_plain._Numerics``'s (the same blocks written out), values and
    gradients, bit for bit; sums of 16 and of 128 terms a block, and one that
    no block divides."""
    from chipbench.references import olmoe_plain

    rng = np.random.default_rng(3)
    for shape_a, shape_b in (((2, 24, 256), (256, 24)), ((24, 48), (48, 8)), ((24, 384), (384, 16)), ((8, 40), (40, 8))):
        a, b = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in (shape_a, shape_b))
        f = lambda num: jax.value_and_grad(lambda a, b: jnp.sum(num("bf16").mm(a, b) ** 2), (0, 1))(a, b)  # noqa: E731
        for got, want in zip(jax.tree.leaves(f(ref._Numerics)), jax.tree.leaves(f(olmoe_plain._Numerics))):
            np.testing.assert_array_equal(got, want)


# -- (b) three steps of make_train_step, parameters and biases -------------------------------


@highest
@pytest.mark.parametrize("impl", ["local", "flash"])
def test_three_adamw_steps_and_the_bias_rule_through_make_train_step(weights, impl):
    from heat_tpu import telemetry

    comm = ht.core.communication.MeshCommunication(devices=jax.devices()[:1])
    model = tiny(remat=True, comm=comm, attn_impl=impl)
    opt = trinity_step.optimizer(OPT)
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        causal_lm_loss(model), has_aux=True, state_rule=balance_bias_rule(C["bias_rate"])
    )
    params = jax.tree.map(jnp.copy, trinity_step.to_system(weights, C))
    state = opt.init({"params": params["params"]})
    # the optimizer holds a moment for every parameter and none for a bias
    assert len(jax.tree.leaves(state)) == 2 * len(jax.tree.leaves(params["params"])) + 2  # and two counts
    rp = jax.tree.map(jnp.copy, weights)
    rs = ref.adamw_init(rp)
    cdf = ref.zipf_cdf(97)
    before = dict(telemetry.get_registry().counters)
    counts = []
    for i in range(3):
        batch = ref.batch(SEED, i, 2, T, cdf)
        params, state, loss, aux = step(params, state, batch)
        loss, aux = read_routing(loss, aux)
        rp, rs, want, parts = ref.train_step(rp, rs, jnp.asarray(batch), C, OPT)
        assert rel(loss, want) < F32, i
        np.testing.assert_array_equal(aux["expert_counts"], parts["expert_counts"])
        counts.append(np.asarray(aux["expert_counts"]))
    after = telemetry.get_registry().counters
    moved = {k: after[k] - before.get(k, 0) for k in after if k.startswith("moe.")}
    assert moved["moe.steps"] == 3 and moved["moe.dropped"] == 0
    assert telemetry.get_registry().watermarks["moe.route_bias_max_abs"] > 0
    got = trinity_step.from_system(params)
    # the biases: the rule, exactly (three additions of +-0.001 to the same float32)
    np.testing.assert_array_equal(got["bias"], rp["bias"])
    by_hand = np.asarray(weights["bias"])
    for c in counts:
        by_hand = by_hand + np.float32(0.001) * np.sign(c.mean(-1, keepdims=True) - c).astype(np.float32)
    np.testing.assert_array_equal(got["bias"], by_hand)
    assert set(np.unique(np.round((np.asarray(got["bias"]) - np.asarray(weights["bias"])) * 1000))) <= {-3, -1, 0, 1, 3, -2, 2}
    # per leaf, the distance between the two over the reference's own move (see tests/test_qwen3_next.py)
    norm = lambda a: float(jnp.sqrt(jnp.sum(a * a)))  # noqa: E731
    for (path, g), w, w0 in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(rp), jax.tree.leaves(weights)):
        if "bias" not in jax.tree_util.keystr(path):
            assert norm(g - w) < 2e-2 * norm(w - w0), jax.tree_util.keystr(path)
    stepped = float(jnp.abs(rp["layers"][0]["wq"] - weights["layers"][0]["wq"]).max())
    assert 4e-4 < stepped < 8e-4  # lr 1e-4, 2e-4, 3e-4: a step that does not step shows


def test_the_bias_gets_no_decay_no_moment_and_no_share_of_the_clip(weights):
    """With gradients of zero and a weight decay, AdamW pulls every leaf it
    holds towards 0; the biases stay where the rule puts them."""
    import optax

    comm = ht.core.communication.MeshCommunication(devices=jax.devices()[:1])
    model = tiny(comm=comm)
    loss_fn = lambda p, tokens: (jnp.zeros(()), {"expert_counts": jnp.ones((6, 16), jnp.int32)})  # noqa: E731
    step = DataParallel(
        model, comm=comm, optimizer=optax.adamw(0.1, weight_decay=0.5), blocking_parameter_updates=True
    ).make_train_step(loss_fn, has_aux=True, state_rule=balance_bias_rule(0.001))
    params = jax.tree.map(jnp.copy, trinity_step.to_system(weights, C))
    state = optax.adamw(0.1, weight_decay=0.5).init({"params": params["params"]})
    new, *_ = step(params, state, np.zeros((1, 8), np.int32))
    np.testing.assert_array_equal(trinity_step.biases_of(new), weights["bias"])  # even counts: sign(0) = 0
    assert rel(new["params"]["block2"]["moe"]["router"], 0.95 * weights["layers"][2]["wr"]) < 1e-6
    with pytest.raises(ValueError, match="has_aux"):
        DataParallel(model, comm=comm, optimizer=optax.sgd(0.1), blocking_parameter_updates=True).make_train_step(
            loss_fn, state_rule=balance_bias_rule(0.001)
        )


# -- (c) the shares of an expert layer add up to the whole layer -------------------------------


def expert_layer(first, held, shared=16):
    return DroplessMoE(
        16, 3, 16, norm_topk=True, shared_d_ff=shared, experts_held=(first, held), score="sigmoid",
        route_scale=2.826, shared_gate=False, select_bias=True,
    )


def share_of(moe, bias, first, held, shared=True):
    """Experts ``first .. first + held - 1`` of an uncut layer's parameters."""
    p = {k: v for k, v in moe.items() if shared or not k.startswith("shared")}
    p.update({k: moe[k][first:first + held] for k in ("w_gate", "w_up", "w_down")})
    return {"params": p, "route_bias": {"bias": bias}}


@highest
def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares (the cell: 128 over 16): the routed parts of
    the four shares plus the shared expert once are the uncut reference layer;
    every share chooses by score plus bias and weighs by the score alone, over
    all k chosen, as the uncut layer does."""
    c = {**C, "num_experts_held": 16, "first_expert_held": 0, "num_hidden_layers": 3}
    w = ref.init_params(SEED + 1, c, 0.3, 0.3)
    lp, moe = w["layers"][2], trinity_step.to_system(w, c)["params"]["block2"]["moe"]
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (16,), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, T, 32), jnp.float32)
    num = ref._Numerics("float32")
    want, _, _, counts, chosen, select, weights = ref._experts(num, c, lp, bias, x.reshape(-1, 32))
    total, held = 0.0, []
    for s in range(4):
        y, sown = expert_layer(4 * s, 4, shared=0).apply(share_of(moe, bias, 4 * s, 4, False), x, mutable=["aux"])
        a = sown["aux"]["moe"][0]
        np.testing.assert_array_equal(a["chosen"], chosen)
        assert rel(a["weights"], weights) < F32 and rel(jnp.sum(a["weights"], -1), jnp.full(2 * T, 2.826)) < 1e-6
        assert int(a["held"]) == int(a["computed"]) == int(counts[4 * s:4 * s + 4].sum())
        np.testing.assert_array_equal(a["expert_counts"], counts)
        total, held = total + y, held + [int(a["held"])]
    assert sum(held) == 2 * T * 3 and min(held) > 0
    with_shared = expert_layer(0, 4).apply(share_of(moe, bias, 0, 4), x)
    without = expert_layer(0, 4, shared=0).apply(share_of(moe, bias, 0, 4, False), x)
    assert rel(total + (with_shared - without), want.reshape(2, T, 32)) < F32
    assert rel(expert_layer(0, 16).apply(share_of(moe, bias, 0, 16), x), want.reshape(2, T, 32)) < F32
    assert "shared_router" not in jax.eval_shape(expert_layer(0, 4).init, jax.random.PRNGKey(0), x)["params"]


# -- (d) rotary on the windowed layers only ---------------------------------------------------


def attention(window, rope):
    return MultiHeadAttention(
        4, "local", True, None, 16, jnp.float32, qk_norm_eps=1e-5, rope_theta=rope, num_kv_heads=2, head_dim=16,
        qk_norm_over="head", norm="rmsnorm", gate=True, window=window,
    )


@highest
def test_a_full_layer_takes_no_positions_and_a_windowed_layer_the_references_rotary(weights):
    """The same weights in both kinds of layer. The windowed layer with rotary
    agrees with the reference's rotate-half attention under its window, the
    full layer without positions with the reference's full form; rotary
    switched on in the full layer, or off in the windowed one, is another
    function. And "no positions at all" taken literally: without rotary a
    windowed layer's output at position t is the same when the whole sequence
    is moved 5 positions along (past the first window, which sees the filler)."""
    lp = weights["layers"][0]
    params = {"params": trinity_step.to_system(weights, C)["params"]["block0"]["attn"]}
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, 32), jnp.float32)
    num = ref._Numerics("float32")
    # layer 0 is sliding, layer 3 full: the reference's two forms on layer 0's weights
    sliding = ref._attention(num, C, lp, x, 0)
    full = ref._attention(num, C, lp, x, 3)
    assert rel(attention(12, 10000.0).apply(params, x), sliding) < F32
    assert rel(attention(None, None).apply(params, x), full) < F32
    # rotary on the full layer, or none on the windowed one, is another function
    assert rel(attention(None, 10000.0).apply(params, x), full) > 1e-2
    assert rel(attention(12, None).apply(params, x), sliding) > 1e-2
    assert rel(ref._attention(num, {**C, "full_rotary": True}, lp, x, 3), full) > 1e-2
    # no positions at all: with a window, position t + 5 of the sequence moved 5 along is position t
    moved = jnp.concatenate([jnp.ones((1, 5, 32)), x], axis=1)
    plain, shifted = attention(12, None).apply(params, x), attention(12, None).apply(params, moved)
    assert rel(shifted[:, 5 + 12:], plain[:, 12:]) < F32
    rotated = attention(12, 10000.0)
    # rotary is relative: the same holds with it, so what tells them apart is the test above
    assert rel(rotated.apply(params, moved)[:, 5 + 12:], rotated.apply(params, x)[:, 12:]) < 1e-4


def test_the_model_rotates_where_it_has_a_window_and_nowhere_else():
    model = trinity_mini()
    assert [model.rotates(i) for i in range(8)] == [True, True, True, False] * 2
    assert [model.window_of(i) for i in range(8)] == [2048, 2048, 2048, None] * 2
    assert not TransformerLM(8, 8, 2, 4, windows=(4, None), rotary=(True, False)).rotates(0)  # learned positions


# -- (e) the selection bias ---------------------------------------------------------------


def test_the_bias_moves_the_choice_and_not_the_weights():
    layer = expert_layer(0, 16)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(1, T, 32)), jnp.float32)
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(7), x)
    assert shapes["route_bias"]["bias"].shape == (16,)  # one bias an expert, beside the parameters (zeros at init)
    params = {"params": jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape) * 0.2, jnp.float32), shapes["params"]
    )}

    def run(bias):
        y, sown = layer.apply({**params, "route_bias": {"bias": bias}}, x, mutable=["aux"])
        return y, sown["aux"]["moe"][0]

    _, plain = run(jnp.zeros(16))
    # a bias towards expert 5 that is larger than any sigmoid: every token takes it
    _, pushed = run(jnp.zeros(16).at[5].set(2.0))
    assert int(pushed["expert_counts"][5]) == T > int(plain["expert_counts"][5])
    scores = jax.nn.sigmoid(x.reshape(T, 32) @ params["params"]["router"])
    picked = jnp.take_along_axis(scores, pushed["chosen"], axis=-1)
    want = picked / picked.sum(-1, keepdims=True) * 2.826  # the scores without the bias, over their sum, scaled
    assert rel(pushed["weights"], want) < F32
    # the same bias on every expert changes nothing at all
    y0, _ = run(jnp.zeros(16))
    y1, same = run(jnp.full(16, 0.25))
    np.testing.assert_array_equal(same["chosen"], plain["chosen"])
    np.testing.assert_array_equal(y0, y1)
    # no gradient reaches it
    g = jax.grad(lambda b: jnp.sum(run(b)[0] ** 2))(jnp.zeros(16).at[5].set(0.01))
    assert not np.any(np.asarray(g))


def test_the_rule_follows_the_sign_on_a_batch_built_to_overload_one_expert():
    """Every token the same id: every token makes the same choice, 3 experts
    take all the assignments and 13 none. The rule lowers the three by one
    update and raises the others."""
    comm = ht.core.communication.MeshCommunication(devices=jax.devices()[:1])
    model = tiny(comm=comm, experts_held=(0, 16))
    c = {**C, "num_experts_held": 16, "first_expert_held": 0}
    weights = ref.init_params(SEED, c, 0.3, 0.3)
    params = trinity_step.to_system(weights, c)
    opt = trinity_step.optimizer(OPT)
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        causal_lm_loss(model), has_aux=True, state_rule=balance_bias_rule(0.001)
    )
    new, _, _, aux = step(params, opt.init({"params": params["params"]}), np.full((1, 8), 3, np.int32))
    counts = np.asarray(aux["expert_counts"])[0]  # the first expert layer sees one input at every position
    bias = np.asarray(trinity_step.biases_of(jax.device_get(new)))[0]
    assert sorted(counts)[-3:] == [8, 8, 8] and counts.sum() == 24
    np.testing.assert_allclose(bias[counts == 8], -0.001, rtol=1e-6)
    np.testing.assert_allclose(bias[counts == 0], 0.001, rtol=1e-6)


def test_a_softmax_router_is_as_it_was_and_an_unknown_score_is_refused():
    x = jnp.zeros((1, 4, 8))
    plain = DroplessMoE(4, 2, 8).init(jax.random.PRNGKey(0), x)
    assert set(plain) == {"params", "aux"} and set(plain["params"]) == {"router", "w_gate", "w_up", "w_down"}
    with pytest.raises(ValueError, match="score"):
        DroplessMoE(4, 2, 8, score="tanh").init(jax.random.PRNGKey(0), x)


# -- (f) the layer pattern and the published configuration ---------------------------------------


def test_trinity_mini_is_the_published_configuration():
    model = trinity_mini()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    p = shapes["params"]
    assert model.num_layers == 32 and model.expert_layers() == tuple(range(2, 32))
    assert p["embed"]["embedding"].shape == (200192, 2048) and p["lm_head"]["kernel"].shape == (2048, 200192)
    a = p["block0"]["attn"]
    assert a["query"]["kernel"].shape == (2048, 32, 256) and a["key"]["kernel"].shape == (2048, 4, 128)
    assert a["out"]["kernel"].shape == (32, 128, 2048) and a["q_norm"]["scale"].shape == (128,)
    assert set(p["block0"]) == {"attn", "ln1", "ln1_post", "ln2", "ln2_post", "gate", "up", "down"}
    assert p["block1"]["gate"]["kernel"].shape == (2048, 6144)
    m = p["block2"]["moe"]
    assert m["router"].shape == (2048, 128) and m["w_gate"].shape == (128, 2048, 1024)
    assert m["shared_down"]["kernel"].shape == (1024, 2048) and "shared_router" not in m
    assert shapes["route_bias"]["block31"]["moe"]["bias"].shape == (128,) and "block1" not in shapes["route_bias"]
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    assert count(p) == 26_123_970_560  # 26.12 B
    # one chip's share as the benchmark's cell cuts it
    cut = jax.eval_shape(
        trinity_mini(num_layers=8, experts_held=(0, 8), vocab_size=25024).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
    )
    assert count(cut["params"]) == 737_480_704
    # the other two models' trees are as they were: no further norm, no bias collection
    other = jax.eval_shape(
        TransformerLM(16, 8, 2, 1, ffn="moe", d_ff=8, num_experts=4, experts_per_token=2).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32),
    )
    assert set(other) == {"params", "aux"} and set(other["params"]["block0"]) == {"attn", "ln1", "ln2", "moe"}


# -- queries and keys to the flash kernels in one pass (PR 50) ------------------------------


@highest
def test_the_pass_before_the_flash_kernels_is_the_xla_lines_and_holds_the_same_parameters(weights, tokens, monkeypatch):
    """``nn/pallas_qk_prep.py`` in the interpreter: a head norm with and without
    rotary, queries beside their gates, two key-value heads, 40 positions."""
    from tests.test_pallas_qk_prep import both_forms

    model = tiny(attn_impl="flash")
    (logits, grads, passes), (k_logits, k_grads, _) = both_forms(
        monkeypatch, model, trinity_step.to_system(weights, C), tokens, causal_lm_loss(model)
    )
    assert passes["xla"] >= 16  # eight layers' queries and keys a trace
    assert rel(k_logits, logits) < F32
    for (path, g), w in zip(jax.tree.leaves_with_path(k_grads["params"]), jax.tree.leaves(grads["params"])):
        assert g.shape == w.shape and rel(g, w) < 1e-4, jax.tree_util.keystr(path)
