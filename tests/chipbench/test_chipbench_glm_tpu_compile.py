"""Compile the ``glm_step`` cell's train step at the published widths for a
described TPU v5e and read, from the compiler's memory analysis and its text,
that it fits one chip and fills it, takes over the state it is given, holds its
flash kernels at 20 heads of 256 in all six latent mixers (the module's among
them), its grouped products and two loops for the head (the trunk's pass and
the module's), the scopes this PR's metrics read, and holds no array of
positions x positions and none of positions x vocabulary; and that the
evaluation the check takes fits the chip. A compile is not a run: nothing here
is a time or a result. Where no TPU compiler can be described the tests skip.

The step is built as ``chipbench/kinds/glm_step.py`` builds it. The flash
kernels ask ``jax.default_backend()`` whether to run in the interpreter, so
the test answers "tpu" for them while it lowers.
"""

import os
import re

import pytest

from chipbench import glm_trace, manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GIB = 2**30
PARAMETERS = 706_518_528


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compiled(topo):
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.communication import MeshCommunication
    from heat_tpu.nn import DataParallel, balance_bias_rule, causal_lm_loss

    parts = manifest.load(REPO)
    config = parts.config(parts.cell("glm47flash-train-8k-1chip"))
    kind = parts.module("kinds", "glm_step")
    comm = MeshCommunication(devices=topo.devices[:1])
    model = kind.build_model(config, comm)
    opt = kind.optimizer(config["optimizer"])
    loss_fn = causal_lm_loss(model, mtp_coef=config["loss"]["mtp"])
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        loss_fn, has_aux=True, state_rule=balance_bias_rule(config["bias_rate"])
    )
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    params = {"params": shapes["params"], "route_bias": shapes["route_bias"]}
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=comm.replicated()), tree
    )
    tokens = jax.ShapeDtypeStruct(
        (config["sequences_per_step"], config["sequence_length"]), jnp.int32, sharding=comm.sharding(0, 2)
    )

    def evaluation(params, tokens):  # the kind's ``evaluation``: what its check runs
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, tokens)
        (hidden, (ahead,)), _ = model.apply(params, tokens, head=False, mtp=True, mutable=["aux"])
        logits = jnp.dot(
            jnp.stack([hidden[:, -256:], ahead[:, -257:-1]]).astype(model.dtype),
            params["params"]["lm_head"]["kernel"].astype(model.dtype), preferred_element_type=jnp.float32,
        )
        return loss, aux, g, logits

    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        program = step.lower(
            placed(params), placed(jax.eval_shape(opt.init, {"params": params["params"]})), tokens
        ).compile()
        evaluation_program = jax.jit(evaluation).lower(placed(params), tokens).compile()
    finally:
        jax.default_backend = backend
    return config, program, evaluation_program


def _total(m):
    return m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes


def test_the_published_width_step_fits_and_fills_one_chip(compiled):
    config, program, _ = compiled
    total = _total(program.memory_analysis())
    assert total < 15 * GIB  # room beside the program for the batch and what the loop reads back
    assert total >= 0.7 * 16e9  # a full-memory step: 8.48 GB of it is state that stays, 11.3 GB with the gradients
    # the figure the configuration's file carries is this compile's, to 2%
    assert abs(total - config["memory_analysis"]["total_bytes"]) < 0.02 * total


def test_parameters_biases_and_optimizer_state_are_donated(compiled):
    """12 bytes a parameter come in (parameter, two moments), with the 320
    biases, and the same buffers go out: the 8.48 GB of state is not held twice."""
    _, program, _ = compiled
    m = program.memory_analysis()
    state = 12 * PARAMETERS + 4 * 5 * 64
    assert m.argument_size_in_bytes >= state and m.alias_size_in_bytes >= state
    assert m.output_size_in_bytes - m.alias_size_in_bytes < 1 << 20


def test_the_step_holds_its_kernels_its_scopes_and_two_loops_for_the_head(compiled):
    config, program, _ = compiled
    text = program.as_text()
    calls = [l.strip() for l in text.splitlines() if "custom-call(" in l]
    full = [l.split(" ")[0].split(".")[0] for l in calls if glm_trace.FULL_ATTENTION.search(l)]
    # six latent mixers x (forward once: the block's checkpoint keeps its output; dq; dk/dv)
    assert sorted(full) == sorted(["%flash_fwd"] * 6 + ["%flash_bwd_dq"] * 6 + ["%flash_bwd_dkv"] * 6)
    assert "%swa_" not in text and "%ragged-dot-none" in text
    # the kernels see 20 heads of 256 whole lanes, keys and values as many as queries: nothing padded, none read by index
    call = next(l for l in calls if l.startswith("%flash_fwd"))
    assert call.count("bf16[2,20,8192,256]") >= 4
    # two loops carry a block of logits over the vocabulary's slice: the trunk's pass through the head and the module's
    loops = [l.strip() for l in text.splitlines() if re.match(r"\s*%while(\.\d+)? = ", l)]
    assert len([l for l in loops if glm_trace.head_loss_rx(config).search(l)]) == 2
    for scope in ("mla.down", "mla.up", "mla.assemble", "mtp.merge", "mtp.block", "mtp.head_loss", "lm.head_loss", "attn.full"):
        assert scope in text, scope
    assert "lm.tied_head" not in text and "block5" in text and "mtp0_eh_proj" in text
    # no array of positions x positions and none of tokens x vocabulary; no tokens x top-k rows of hidden features
    assert "[8192,8192]" not in text and "[16384,19360]" not in text and "[65536,2048]" not in text
    # the first window of held rows is held_window x an even share of the 65,536 assignments (8,192 rows), behind a branch
    rows = min(65536, int(config["held_window"] * 8192))
    assert f"[{rows},1536]" in text and (" conditional(" in text) == (rows < 65536)


def test_the_checks_evaluation_fits_the_chip_where_the_moments_step_aside(compiled):
    """``correct`` takes the program's gradients at the parameters the timed
    step is about to consume: gradients out (4 bytes a parameter). It fits the
    chip alone; beside both AdamW moments (8 bytes a parameter) it leaves
    little over a GiB of the chip's 15.75, too little for what else the check
    holds, which is why the kind's state lets the moments step aside
    (``trinity_step.State.grads``)."""
    _, _, evaluation_program = compiled
    m = evaluation_program.memory_analysis()
    assert m.output_size_in_bytes >= 4 * PARAMETERS
    assert _total(m) < 10 * GIB and 15.75 * GIB - (_total(m) + 8 * PARAMETERS) < 1.5 * GIB
