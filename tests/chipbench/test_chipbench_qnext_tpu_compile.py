"""Compile the ``qnext_step`` cell's train step at the published widths for a
described TPU v5e and read, from the compiler's memory analysis, that it fits
one chip, fills it, takes over the state it is given, holds its kernels and its
loops by name, and that the gradients the check takes fit beside the optimizer
state. A compile is not a run: nothing here is a time or a result.

The step is built as ``chipbench/kinds/qnext_step.py`` builds it. The flash
kernels ask ``jax.default_backend()`` whether to run in the interpreter, so
the test answers "tpu" for them while it lowers.
"""

import os
import re

import pytest

from chipbench import manifest, qnext_trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GIB = 2**30
PARAMETERS = 625_667_136


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
    from heat_tpu.nn import DataParallel, causal_lm_loss

    parts = manifest.load(REPO)
    config = parts.config(parts.cell("qwen3next-train-8k-1chip"))
    kind = parts.module("kinds", "qnext_step")
    comm = MeshCommunication(devices=topo.devices[:1])
    model = kind.build_model(config, comm)
    opt = kind.optimizer(config["optimizer"])
    loss_fn = causal_lm_loss(
        model, load_balance_coef=config["loss"]["load_balance"], router_z_coef=config["loss"]["router_z"]
    )
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        loss_fn, has_aux=True
    )
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    params = {"params": shapes["params"]}
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=comm.replicated()), tree
    )
    tokens = jax.ShapeDtypeStruct(
        (config["sequences_per_step"], config["sequence_length"]), jnp.int32, sharding=comm.sharding(0, 2)
    )

    def grads(params, tokens):  # the kind's ``evaluation``: what its check runs beside the resident optimizer state
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, tokens)
        hidden = model.apply(params, tokens, head=False, mutable=["aux"])[0]
        logits = jnp.dot(
            hidden[:, -256:].astype(model.dtype), params["params"]["lm_head"]["kernel"].astype(model.dtype),
            preferred_element_type=jnp.float32,
        )
        return loss, aux, g, logits

    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        program = step.lower(placed(params), placed(jax.eval_shape(opt.init, params)), tokens).compile()
        grads_program = jax.jit(grads).lower(placed(params), tokens).compile()
    finally:
        jax.default_backend = backend
    return config, program, grads_program


def _total(m):
    return m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes


def test_the_published_width_step_fits_and_fills_one_chip(compiled):
    config, program, _ = compiled
    total = _total(program.memory_analysis())
    assert total < 15 * GIB  # room beside the program for the batch and what the loop reads back
    assert total >= 0.7 * 16e9  # a full-memory step: 10.0 GB of it is state
    # the figure the configuration's file carries is this compile's, to 2%
    assert abs(total - config["memory_analysis"]["total_bytes"]) < 0.02 * total


def test_parameters_and_optimizer_state_are_donated(compiled):
    """12 bytes a parameter come in (parameter, two moments) and the same
    buffers go out: the 7.5 GB of state is not held twice."""
    _, program, _ = compiled
    m = program.memory_analysis()
    state = 12 * PARAMETERS
    assert m.argument_size_in_bytes >= state and m.alias_size_in_bytes >= state
    assert m.output_size_in_bytes - m.alias_size_in_bytes < 1 << 20


def test_the_step_holds_its_kernels_and_loops_by_name(compiled):
    config, program, _ = compiled
    text = program.as_text()
    for name in ("%flash_fwd", "%flash_bwd_dq", "%flash_bwd_dkv", "%ragged-dot-none"):
        assert name in text, name
    # the flash kernels read 2 key-value heads of 256 for 16 query heads: no repeated copy
    call = next(l for l in text.splitlines() if l.lstrip().startswith("%flash_fwd") and "custom-call(" in l)
    assert "bf16[2,16,8192,256]" in call and "bf16[2,2,8192,256]" in call
    # the loops the readers tell apart, by what they carry
    loops = [l.strip() for l in text.splitlines() if re.match(r"\s*%while(\.\d+)? = ", l)]
    scan, mixer, head = (rx(config) for rx in (qnext_trace.gdn_scan_rx, qnext_trace.gdn_mixer_rx, qnext_trace.head_loss_rx))
    assert len([l for l in loops if head.search(l)]) == 1
    assert len([l for l in loops if mixer.search(l)]) == 9  # three layers: forward, forward again, backward
    assert len([l for l in loops if scan.search(l)]) >= 12  # a scan over chunks and its transpose a mixer pass
    assert all(sum(bool(rx.search(l)) for rx in (scan, mixer, head)) <= 1 for l in loops)
    # no array of tokens x top-k rows of hidden features, no full logits, no state a position
    assert "[163840,2048]" not in text and "f32[16384,18992]" not in text and "f32[2,8192,18992]" not in text
    assert "[20480,2048]" in text  # one window of held rows
    assert "[8192,1,32,128,128]" not in text and "f32[128,1,32,128,128]" in text  # a state a chunk


def test_the_checks_gradients_fit_beside_the_optimizer_state(compiled):
    """``correct`` takes the program's gradients at the parameters the timed
    step is about to consume, with both AdamW moments (8 bytes a parameter)
    still on the chip: together under the 15.75 GiB a v5e chip gives."""
    _, _, grads_program = compiled
    m = grads_program.memory_analysis()
    assert m.output_size_in_bytes >= 4 * PARAMETERS
    assert _total(m) + 8 * PARAMETERS < 15 * GIB
