"""Ouro-2.6B through ``ht.nn`` against its plain reference
(``chipbench/references/ouro_plain.py``), on the CPU at tiny widths (hidden 64, 4
heads of 16, 2 blocks, 3 and 4 passes, vocabulary 128) with seeded weights: (a)
the whole model: every exit's logits, the exit distribution (sums to 1, the
closed form), the loss and every gradient leaf, in float32 with ``local`` and
``flash`` attention, and in mixed precision with the control farther; (b) **the
loop ties to the model**: the looped program is the same blocks written out
``passes`` times with untied copies of the weights, and a tied weight's gradient
the sum of its copies'; (c) ``passes=1`` without a gate is today's
``TransformerLM``, bit for bit and program for program; (d) ``ln_f`` stands
inside the loop; (e) what ``exit_beta`` moves; (f) the reference's written-out
backpropagation is autodiff of its plain loss, the controls' too; (g) two steps
of ``make_train_step`` against the reference's AdamW; (h) the builder, the
counters, the scopes and the refusals. A CPU run gives results and counts, no time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from chipbench.kinds import lm_step, ouro_step
from chipbench.references import ouro_plain as ref
from heat_tpu import telemetry
from heat_tpu.nn import (
    DataParallel, TransformerBlock, TransformerLM, causal_lm_loss, exit_distribution, ouro_2_6b, read_exits, read_routing,
)

C = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16, intermediate_size=96, vocab_size=128,
    num_hidden_layers=2, rms_norm_eps=1e-6, rope_theta=1000000, total_ut_steps=4,
)
COEF = {"beta": 0.05}
OPT = {"lr": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip": 1.0, "warmup_steps": 4, "coef": COEF}
SEED, T = 31, 40

# float32 against float32 at "highest": the same sums in another order through
# 2 blocks x 4 passes; observed 1e-6..4e-6
F32 = 2e-5


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    yield
    jax.clear_caches()


def tiny(**fields):
    arch = dict(
        num_layers=2, vocab_size=128, d_model=64, num_heads=4, d_ff=96, max_len=64, init_std=0.02, out_init_std=0.01,
        dtype=jnp.float32, accum_dtype=None, attn_impl="local", block_size=16,
    )
    return ouro_2_6b(**{**arch, **fields})


def config(passes):
    return {**C, "total_ut_steps": passes}


@pytest.fixture(scope="module")
def weights():
    # norm gains and the gate's bias away from their initial 1 and 0, and a gate wide enough that the exit
    # distribution differs from position to position: a gain in the wrong place or a gate misread shows
    w = ref.init_params(SEED, C, 0.15, 0.1, 0.5)
    key = jax.random.PRNGKey(SEED)
    leaves, tree = jax.tree.flatten(w)
    leaves = [
        a + 0.2 * jax.random.normal(jax.random.fold_in(key, i), a.shape, jnp.float32) if a.ndim <= 1 else a
        for i, a in enumerate(leaves)
    ]
    return jax.tree.unflatten(tree, leaves)


@pytest.fixture(scope="module")
def tokens():
    return ref.batch(SEED, 0, 2, T, ref.zipf_cdf(C["vocab_size"]))


def worst(got, want):
    gaps = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), got, want)
    return max(jax.tree.leaves(gaps))


def program(model, params, tokens, beta=COEF["beta"]):
    """Loss, aux, gradients (the reference's layout), every exit's logits and
    the exit distribution of the program."""
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.value_and_grad(causal_lm_loss(model, exit_beta=beta), has_aux=True)(params, tokens)
        exits, gates = model.apply(params, tokens, head=False)
        logits = jnp.dot(exits.astype(model.dtype), params["params"]["lm_head"]["kernel"].astype(model.dtype),
                         preferred_element_type=jnp.float32)
    return loss, aux, ouro_step.from_system(grads), logits, jnp.exp(exit_distribution(gates))


def reference(weights, tokens, c, coef=COEF, products="float32"):
    with jax.default_matmul_precision("highest"):
        (loss, parts), grads = jax.value_and_grad(ref.loss_parts, has_aux=True)(weights, tokens, c, coef, products)
        logits = ref.logits_of(weights, tokens, c, products)
    return loss, parts, grads, logits


# -- (a) the whole model -------------------------------------------------------------


@pytest.mark.parametrize("passes", [3, 4])
@pytest.mark.parametrize("attn", ["local", "flash"])
def test_program_is_the_reference_in_float32(weights, tokens, passes, attn):
    c = config(passes)
    model = tiny(passes=passes, attn_impl=attn, remat=attn == "flash")
    loss, aux, grads, logits, pdf = program(model, ouro_step.to_system(weights, c), tokens)
    w_loss, parts, w_grads, w_logits = reference(weights, tokens, c)
    assert logits.shape == (passes, 2, T, C["vocab_size"]) and pdf.shape == (passes, 2, T)
    for t in range(passes):  # every exit, through the one head
        assert ref.rel_gap(logits[t], w_logits[t]) < F32, t
    assert float(jnp.max(jnp.abs(pdf - parts["pdf"]))) < F32
    assert abs(float(loss) - float(w_loss)) < F32 * abs(float(w_loss))
    for name in ("ce", "exit_entropy", "expected_pass"):
        assert abs(float(aux[name]) - float(parts[name])) < F32 * abs(float(parts[name])), name
    assert float(aux["load_balance"]) == 0.0 and float(aux["router_z"]) == 0.0
    gaps = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), grads, w_grads)
    assert max(jax.tree.leaves(gaps)) < 10 * F32, gaps  # every leaf, the gate's two among them
    assert float(jnp.max(jnp.abs(w_grads["w_gate"]))) > 0 and float(jnp.abs(w_grads["b_gate"])) > 0


@pytest.mark.parametrize("passes", [3, 4])
def test_exit_distribution_sums_to_one_and_is_the_closed_form(passes):
    gates = 3.0 * jax.random.normal(jax.random.PRNGKey(passes), (passes - 1, 2, 7), jnp.float32)
    log_p = exit_distribution(gates)
    p = np.asarray(jnp.exp(log_p), np.float64)
    assert log_p.shape == (passes, 2, 7) and log_p.dtype == jnp.float32
    np.testing.assert_allclose(p.sum(0), 1.0, atol=2e-6)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(gates, np.float64)))
    stay = np.cumprod(np.concatenate([np.ones_like(lam[:1]), 1.0 - lam]), axis=0)  # prod_{j<t} (1 - lam_j)
    want = np.concatenate([lam * stay[:-1], stay[-1:]])
    np.testing.assert_allclose(p, want, rtol=2e-5, atol=1e-9)
    # a gate that is certain either way neither overflows nor gives a NaN
    sure = exit_distribution(jnp.asarray([[200.0], [-200.0], [0.0]], jnp.float32))
    assert np.all(np.isfinite(np.asarray(jnp.exp(sure)))) and float(jnp.exp(sure)[0, 0]) == 1.0


def test_mixed_precision_is_nearer_the_reference_than_the_control(weights, tokens):
    """bfloat16 operands with float32 accumulation, stream, norms, gate and
    distribution (the configuration's guarantee) against the float32 reference,
    and the reference a precision below (bfloat16 throughout) against it."""
    params = ouro_step.to_system(weights, C)
    loss, _, grads, logits, pdf = program(tiny(dtype=jnp.bfloat16, accum_dtype=jnp.float32), params, tokens)
    w_loss, parts, w_grads, w_logits = reference(weights, tokens, C)
    c_loss, c_parts, c_grads, c_logits = reference(weights, tokens, C, products="bf16")
    got = max(ref.rms_gap(logits[t], w_logits[t]) for t in range(4))
    control = max(ref.rms_gap(c_logits[t], w_logits[t]) for t in range(4))
    assert got < 0.03 and control > 1.3 * got, (got, control)
    assert float(jnp.max(jnp.abs(pdf - parts["pdf"]))) < float(jnp.max(jnp.abs(c_parts["pdf"] - parts["pdf"])))
    assert abs(float(loss) - float(w_loss)) < 2e-3 * abs(float(w_loss))
    norms, w_norms = ref.group_norms(grads), ref.group_norms(w_grads)
    assert max(ref.rel_gap(norms[g], w_norms[g]) for g in ref.GROUPS) < 0.05


def test_against_the_stated_precision_the_program_stands_apart_from_a_precision_below(weights, tokens):
    """``last_exits`` at ``operands`` is the guarantee itself (bfloat16 operands,
    float32 accumulation and everything else): the program rounds the same
    values at the same places and lies nearer to it than to float32, and the
    control, or the program with its stream, norms and results in bfloat16,
    lie twice as far from it or more: the benchmark's ``precision_gap``."""
    params = ouro_step.to_system(weights, C)
    logits = program(tiny(dtype=jnp.bfloat16, accum_dtype=jnp.float32), params, tokens)[3]
    below = program(tiny(dtype=jnp.bfloat16, accum_dtype=None), params, tokens)[3]
    exact, stated, control = (ref.last_exits(weights, tokens, C, T, products)[1] for products in ("float32", "operands", "bf16"))
    assert worst(exact, ref.logits_of(weights, tokens, C)) < 10 * F32  # the forward pass a block a program is the plain one
    gap = lambda got, want: max(ref.rms_gap(got[t], want[t]) for t in range(4))  # noqa: E731
    assert gap(stated, exact) > 1e-3  # the stated precision is not float32 ...
    assert gap(logits, stated) < 0.6 * gap(logits, exact)  # ... and the program keeps it
    assert gap(control, stated) > 2 * gap(logits, stated) and gap(below, stated) > 2 * gap(logits, stated)


# -- (b) the loop ties to the model -----------------------------------------------------


def written_out(model, copies, embed, tokens):
    """The same blocks applied ``passes`` times from ``copies`` (one parameter
    tree a pass, untied), ``ln_f`` after every pass: the exits' hidden states."""
    block = TransformerBlock(
        model.num_heads, attn_impl="local", block_size=16, dtype=model.dtype, norm="rmsnorm", norm_eps=model.norm_eps,
        rope_theta=model.rope_theta, d_ff=model.d_ff, init_std=0.02, sandwich_norm=True,
    )
    x = embed[tokens]
    exits = []
    for p in copies:
        for i in range(model.num_layers):
            x = block.apply({"params": p[f"block{i}"]}, x)
        scale = p["ln_f"]["scale"]
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + model.norm_eps) * scale
        exits.append(x)
    return jnp.stack(exits)


@pytest.mark.parametrize("passes", [3, 4])
def test_the_loop_is_the_blocks_written_out_and_a_tied_gradient_the_sum_of_the_copies(weights, tokens, passes):
    model = tiny(passes=passes)
    params = ouro_step.to_system(weights, config(passes))
    tied = {k: v for k, v in params["params"].items() if k.startswith("block") or k == "ln_f"}
    embed = params["params"]["embed"]["embedding"]
    cot = jax.random.normal(jax.random.PRNGKey(5), (passes, 2, T, C["hidden_size"]), jnp.float32)

    def looped(tied):
        exits, _ = model.apply({"params": {**params["params"], **tied}}, tokens, head=False)
        return jnp.sum(exits * cot), exits

    def untied(copies):
        exits = written_out(model, copies, embed, tokens)
        return jnp.sum(exits * cot), exits

    with jax.default_matmul_precision("highest"):
        (_, exits), g_tied = jax.value_and_grad(looped, has_aux=True)(tied)
        (_, w_exits), g_copies = jax.value_and_grad(untied, has_aux=True)([tied] * passes)
    assert ref.rel_gap(exits, w_exits) < F32
    summed = jax.tree.map(lambda *g: sum(g), *g_copies)
    assert worst(g_tied, summed) < 10 * F32
    # and no copy's gradient alone is the tied one: every pass carries its share
    assert all(worst(g_tied, g) > 0.05 for g in g_copies)


# -- (c) one pass without a gate is today's model ------------------------------------------


def test_one_pass_without_a_gate_is_the_model_as_it_was(tokens):
    fields = dict(vocab_size=128, d_model=64, num_heads=4, num_layers=2, max_len=64, norm="rmsnorm", positions="rope",
                  d_ff=96, sandwich_norm=True)
    model, explicit = TransformerLM(**fields), TransformerLM(**fields, passes=1)
    params = model.init(jax.random.PRNGKey(0), tokens)
    assert "exit_gate_kernel" not in params["params"]
    # the program of a model that names the defaults is the program of one that does not: no loop, no gate
    texts = [str(jax.make_jaxpr(lambda p: m.apply(p, tokens))(params)) for m in (model, explicit)]
    assert texts[0] == texts[1] and "exit_gate" not in texts[0] and "lm.loop" not in texts[0]
    # and its output is the blocks applied in turn, bit for bit
    block = TransformerBlock(4, norm="rmsnorm", rope_theta=10000.0, d_ff=96, sandwich_norm=True)
    p = params["params"]
    x = p["embed"]["embedding"][tokens]
    for i in range(2):
        x = block.apply({"params": p[f"block{i}"]}, x)
    import flax.linen as nn

    x = nn.RMSNorm(epsilon=1e-6).apply({"params": p["ln_f"]}, x)
    logits = nn.Dense(128, use_bias=False).apply({"params": p["lm_head"]}, x)
    np.testing.assert_array_equal(np.asarray(model.apply(params, tokens)), np.asarray(logits))


def test_with_its_head_a_looped_model_gives_its_last_exits_logits(weights, tokens):
    model = tiny()
    params = ouro_step.to_system(weights, C)
    exits, gates = model.apply(params, tokens, head=False)
    assert exits.shape == (4, 2, T, 64) and gates.shape == (3, 2, T) and gates.dtype == jnp.float32
    # with the head: the last exit's logits (the published early_exit_threshold 1 leaves at the last pass)
    import flax.linen as nn

    logits = nn.Dense(C["vocab_size"], use_bias=False).apply({"params": params["params"]["lm_head"]}, exits[-1])
    np.testing.assert_array_equal(np.asarray(model.apply(params, tokens)), np.asarray(logits))
    # the gate follows from the passes: a model of one pass has none, and is trained on its one exit as any TransformerLM
    once = tiny(passes=1)
    bare = {"params": {k: v for k, v in params["params"].items() if not k.startswith("exit_gate")}}
    assert ref.rel_gap(once.apply(bare, tokens, head=False), exits[0]) < 10 * F32  # the loop's first pass, compiled apart
    loss, aux = causal_lm_loss(once)(bare, tokens)
    assert "expected_pass" not in aux and np.isfinite(float(loss))


# -- (d) ln_f inside the loop, (e) beta ---------------------------------------------------


def test_ln_f_stands_inside_the_loop(weights, tokens):
    """The second pass reads the first pass's final norm: against the
    reference with the norm outside the loop the later exits are elsewhere."""
    exits, _ = tiny().apply(ouro_step.to_system(weights, C), tokens, head=False)
    with jax.default_matmul_precision("highest"):
        inside, outside = ref.hidden_states(weights, tokens, C), ref.hidden_states(weights, tokens, {**C, "ln_f_once": True})
    assert ref.rel_gap(exits, inside) < 10 * F32
    assert ref.rel_gap(exits[0], outside[0]) < 10 * F32  # the first pass is the same either way
    assert min(ref.rms_gap(exits[t], outside[t]) for t in (1, 2, 3)) > 0.05


def test_what_beta_moves(weights, tokens):
    """``loss(beta) = E[ce] - beta H``: the loss moves by ``-H`` a unit of
    beta; the head, which the entropy does not reach, keeps its gradient to
    the bit; the gate's moves, and with it (through ``h . w_g``) the blocks'."""
    params = ouro_step.to_system(weights, C)
    model = tiny()
    at = {beta: program(model, params, tokens, beta) for beta in (0.0, 0.05, 0.5)}
    entropy = float(at[0.05][1]["exit_entropy"])
    assert entropy > 0.1
    for beta in (0.05, 0.5):
        assert abs(float(at[beta][0]) - (float(at[0.0][0]) - beta * entropy)) < 1e-5
        np.testing.assert_array_equal(np.asarray(at[beta][2]["head"]), np.asarray(at[0.0][2]["head"]))
        assert ref.rel_gap(at[beta][2]["w_gate"], at[0.0][2]["w_gate"]) > 0.01
        assert float(jnp.abs(at[beta][2]["b_gate"] - at[0.0][2]["b_gate"])) > 1e-4
    assert ref.rel_gap(at[0.5][2]["layers"][0]["wq"], at[0.0][2]["layers"][0]["wq"]) > 1e-4


# -- (f) the reference's own backpropagation ------------------------------------------------


@pytest.mark.parametrize("control", [{}, {"passes_run": 3}, {"ln_f_once": True}, {"last_exit_only": True}, {"stop_gate": True}])
def test_the_references_written_out_gradients_are_autodiff_of_its_loss(weights, tokens, control):
    c = {**C, **control}
    with jax.default_matmul_precision("highest"):
        (w_loss, w_parts), w_grads = jax.value_and_grad(ref.loss_parts, has_aux=True)(weights, tokens, c, COEF)
        loss, parts, grads = ref._gradients(weights, tokens, c, COEF, "float32")
    assert abs(float(loss) - float(w_loss)) < F32 * abs(float(w_loss))
    assert float(jnp.max(jnp.abs(parts["pdf"] - w_parts["pdf"]))) < F32
    assert worst(grads, w_grads) < 10 * F32
    np.testing.assert_allclose(np.asarray(parts["pdf"]).sum(0), 1.0, atol=2e-6)  # every control's is a distribution
    if control == {"stop_gate": True}:
        assert float(jnp.max(jnp.abs(grads["w_gate"]))) == 0.0 and float(grads["b_gate"]) == 0.0


def test_the_one_use_control_keeps_the_last_passes_gradient(weights, tokens):
    with jax.default_matmul_precision("highest"):
        _, _, whole = ref._gradients(weights, tokens, C, COEF, "float32")
        _, _, one = ref._gradients(weights, tokens, {**C, "one_use": True}, COEF, "float32")
    assert worst(one["head"], whole["head"]) == 0.0 and worst(one["embed"], whole["embed"]) == 0.0
    norms, w_norms = ref.group_norms(one), ref.group_norms(whole)
    assert ref.rel_gap(norms["attention"], w_norms["attention"]) > 0.1


# -- (g) the step ---------------------------------------------------------------------------


def test_two_steps_of_make_train_step_are_the_references(weights, tokens):
    comm = ht.MeshCommunication(devices=jax.devices()[:1])
    model = tiny(comm=comm, remat=True)
    opt = lm_step.optimizer(OPT)
    dp = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True)
    step = dp.make_train_step(causal_lm_loss(model, exit_beta=COEF["beta"]), has_aux=True)
    params = jax.tree.map(jnp.copy, ouro_step.to_system(weights, C))
    state = opt.init(params)
    w_params, w_state = jax.tree.map(jnp.copy, weights), ref.adamw_init(weights)
    cdf = ref.zipf_cdf(C["vocab_size"])
    telemetry.get_registry().counters.clear()
    for i in range(2):
        batch = ref.batch(SEED, i, 2, T, cdf)
        with jax.default_matmul_precision("highest"):
            params, state, loss, aux = step(params, state, batch)
            w_params, w_state, w_loss, parts = ref.train_step(w_params, w_state, batch, C, OPT)
        loss, aux = read_exits(loss, aux)
        assert abs(float(loss) - float(w_loss)) < F32 * abs(float(w_loss)), i
        assert abs(float(aux["expected_pass"]) - float(parts["expected_pass"])) < F32 * 4
    # AdamW's first steps move an entry by the rate in the sign of m / sqrt(v): compared as updates
    moved = jax.tree.map(lambda a, b: a - b, ouro_step.from_system(params), weights)
    w_moved = jax.tree.map(lambda a, b: a - b, w_params, weights)
    assert worst(moved, w_moved) < 0.02
    counters = telemetry.get_registry().counters
    assert counters["lm.exit.steps"] == 2 and 2 * 1.0 < counters["lm.exit.expected_pass"] < 2 * 4.0


def test_the_exit_counters_are_read_exits_own(weights, tokens):
    """``read_routing`` (the expert layer's) knows nothing of the gate: ``read_exits`` counts it."""
    loss, aux = causal_lm_loss(tiny())(ouro_step.to_system(weights, C), tokens)
    counters = telemetry.get_registry().counters
    counters.clear()
    read_routing(loss, aux)
    assert not any(k.startswith("lm.exit.") for k in counters)
    _, got = read_exits(loss, aux)
    assert counters["lm.exit.steps"] == 1 and counters["lm.exit.expected_pass"] == float(got["expected_pass"])


# -- (h) the builder, the counters, the scopes, the refusals -----------------------------------


def test_the_builder_names_the_published_model():
    m = ouro_2_6b()
    assert (m.vocab_size, m.d_model, m.num_heads, m.num_layers, m.d_ff, m.max_len) == (49152, 2048, 16, 48, 5632, 65536)
    assert (m.passes, m.sandwich_norm, m.norm, m.norm_eps, m.rope_theta) == (4, True, "rmsnorm", 1e-6, 1e6)
    assert m.num_kv_heads is None and m.head_dim is None and not m.qk_norm and not m.tie_embeddings and m.ffn == "swiglu"
    assert m.dtype == jnp.bfloat16 and m.accum_dtype == jnp.float32 and m.attn_impl == "flash"
    assert abs(m.out_init_std - 0.02 / np.sqrt(2 * 48 * 4)) < 1e-12
    shapes = jax.eval_shape(lambda: ouro_2_6b(num_layers=1).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    p = shapes["params"]
    assert sum(x.size for x in jax.tree.leaves(p["block0"])) == 51_388_416  # a block: 4 x 2048^2 + 3 x 2048 x 5632 + 4 norms
    assert p["exit_gate_kernel"].shape == (2048, 1) and p["exit_gate_bias"].shape == (1,)
    assert sorted(k for k in p["block0"] if k.startswith("ln")) == ["ln1", "ln1_post", "ln2", "ln2_post"]


def test_counters_and_scopes_of_the_loop(weights, tokens):
    model = tiny()
    params = ouro_step.to_system(weights, C)
    reg = telemetry.get_registry()
    reg.counters.clear()
    lowered = jax.jit(jax.value_and_grad(causal_lm_loss(model), has_aux=True)).lower(params, tokens)
    assert reg.counters["lm.loop.passes"] == 4  # once a trace
    text = lowered.as_text(debug_info=True)
    for scope in ("lm.loop", "lm.exit_gate", "lm.head_loss", "lm.body"):
        assert scope in text, scope
    # flax's frames for ``_looped`` and the scanned function stand in the op names and are no scope of the map
    from heat_tpu.telemetry import hlo

    assert "TransformerLM._looped" in text and "TransformerLM.one_pass" in text
    split = hlo.split_op_name(
        "jit(f)/jvp(lm.body)/TransformerLM/TransformerLM._looped/lm.loop/while/body/TransformerLM.one_pass/checkpoint/block1/attn/query/dot_general"
    )
    assert (split["modules"], split["scopes"], split["pass"]) == ("TransformerLM/block1/attn/query", ("lm.body", "lm.loop"), "forward")
    # the loop holds each block's body once: one scan over the four passes (the others are the attention's blocks)
    jaxpr = str(jax.make_jaxpr(lambda p: model.apply(p, tokens, head=False))(params))
    assert jaxpr.count("length=4") == 1


@pytest.mark.parametrize("fields, message", [
    (dict(passes=0), "passes"),
    (dict(ffn="moe", num_experts=4, experts_per_token=2), "SwiGLU"), (dict(mtp_modules=1), "prediction module"),
])
def test_what_a_looped_stack_refuses(tokens, fields, message):
    with pytest.raises(ValueError, match=message):
        tiny(**fields).init(jax.random.PRNGKey(0), tokens)


# -- queries and keys to the flash kernels in one pass (PR 50) ------------------------------


def test_the_pass_before_the_flash_kernels_is_the_xla_lines_and_holds_the_same_parameters(weights, tokens, monkeypatch):
    """``nn/pallas_qk_prep.py`` in the interpreter: rotary alone, in a looped
    stack under its checkpoint."""
    from tests.test_pallas_qk_prep import both_forms

    model = tiny(attn_impl="flash", remat=True)
    with jax.default_matmul_precision("highest"):
        (exits, grads, passes), (k_exits, k_grads, _) = both_forms(
            monkeypatch, model, ouro_step.to_system(weights, C), jnp.asarray(tokens),
            causal_lm_loss(model, exit_beta=COEF["beta"]), apply=lambda p: model.apply(p, jnp.asarray(tokens), head=False)[0],
        )
    assert passes["xla"] >= 4  # two blocks' queries and keys a trace of the loop's body
    assert ref.rel_gap(k_exits, exits) < F32
    assert worst(k_grads["params"], grads["params"]) < 10 * F32
