"""A rematerialised block keeps its attention core's output and log-sum-exp:
``TransformerLM(remat=True)`` checkpoints every block under a policy that saves
the two arrays ``pallas_attention._flash_fwd`` names, so the backward pass runs
a block's forward again without the flash forward kernel. On the CPU the
kernels run in the Pallas interpreter; the gradients are those of a block
rematerialised whole, to the bit, because the kept arrays are what the second
run of the kernel would have written.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat_tpu import telemetry
from heat_tpu.nn import transformer
from heat_tpu.nn.transformer import TransformerLM
from heat_tpu.parallel import pallas_attention
from tests.test_flash_window import _pallas_calls

FORWARD = ("flash_fwd", "swa_fwd")


def lm(**fields):
    arch = dict(
        vocab_size=61, d_model=32, num_heads=4, num_kv_heads=2, num_layers=2, max_len=64, attn_impl="flash",
        block_size=16, remat=True, positions="rope", norm="rmsnorm",
    )
    return TransformerLM(**{**arch, **fields})


def loss_of(model, tokens):
    def loss(params):
        logits = model.apply(params, tokens[:, :-1]).astype(jnp.float32)
        picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)

    return loss


def kernels_of(loss, params):
    """The names of the ``pallas_call``s in the gradient's jaxpr, in order."""
    return [e.params["name"] for e in _pallas_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, [])]


CASES = {
    "full": dict(),
    "window-and-full": dict(windows=(16, None)),
    "two-pass-backward": dict(flash_bwd_impl="two_pass", windows=(16, None)),
    "with-dots": dict(remat_policy="dots"),
    "ragged-length": dict(windows=(16, None), length=41),  # 40 positions: the last block of queries is half padding
    "batch-over-the-mesh": dict(mesh=True, windows=(16, None)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_are_the_whole_rematerialisations_with_one_forward_kernel_a_block(case, monkeypatch):
    fields = dict(CASES[case])
    length = fields.pop("length", 49)
    if fields.pop("mesh", False):
        import heat_tpu as ht

        fields["comm"] = ht.get_comm()
    model = lm(**fields)
    batch = 8 if "comm" in fields else 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, length), 0, 61)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])
    loss = loss_of(model, tokens)
    kept, names = jax.grad(loss)(params), kernels_of(loss, params)
    # nothing named: the policy saves nothing and every block is rematerialised whole, the parent's form
    monkeypatch.setattr(transformer, "KEPT_RESIDUALS", ())
    whole, names_whole = jax.grad(loss)(params), kernels_of(loss, params)
    for (path, a), b in zip(jax.tree.leaves_with_path(kept), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))
    forward = lambda found: sorted(n for n in found if n in FORWARD)  # noqa: E731
    a_block = ["flash_fwd", "flash_fwd"] if "windows" not in fields else ["flash_fwd", "swa_fwd"]
    assert forward(names) == a_block and forward(names_whole) == sorted(2 * a_block)
    # the backward kernels are untouched: one fused (or, forced, one dq and one dk/dv) a block, either way
    assert sorted(n for n in names if n not in FORWARD) == sorted(n for n in names_whole if n not in FORWARD)
    assert len(names) == len(names_whole) - 2


@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
@pytest.mark.parametrize("t", [64, 40], ids=["whole-blocks", "padded"])
def test_the_kept_log_sum_exp_is_lane_zero_of_the_kernels_one_float_a_row(window, t):
    b, h, h_kv, d = 2, 4, 2, 16
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, heads, t, d), jnp.float32)
        for i, heads in enumerate((h, h_kv, h_kv))
    )
    static = (d**-0.5, True, t, 16, 16, True)  # scale, causal, kv_valid, block_q, block_k, interpret
    out, lanes = pallas_attention._flash_forward(q, k, v, *static, return_lse=True, window=window)
    primal, (_, _, _, out_kept, lse) = pallas_attention._flash_fwd(q, k, v, *static, "auto", window)
    assert lanes.shape == (b, h, -(-t // 16) * 16, 128)  # the kernel's layout: padded rows, a value broadcast over the lanes
    assert lse.shape == (b, h, t) and lse.dtype == jnp.float32
    np.testing.assert_array_equal(lse, lanes[:, :, :t, 0])
    np.testing.assert_array_equal(out_kept, out)
    np.testing.assert_array_equal(primal, out)


def test_the_forward_rule_names_its_two_residuals_and_nothing_else():
    q = jax.ShapeDtypeStruct((1, 32, 2, 16), jnp.float32)
    f = lambda q, k, v: jnp.sum(pallas_attention.flash_attention(q, k, v, causal=True, interpret=True))  # noqa: E731
    text = str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, q, q))
    assert [text.count(f"name={n}]") for n in pallas_attention.KEPT_RESIDUALS] == [1, 1]
    assert text.count("name[") == 2


@pytest.mark.parametrize("fields, kept", [
    (dict(num_layers=3), 3),
    (dict(num_layers=8, windows=(16, 16, 16, None)), 8),
    (dict(num_layers=3, remat=False), 0),  # no checkpoint, nothing to keep
    (dict(num_layers=3, attn_impl="local"), 0),  # the XLA form names nothing: rematerialised whole
    (dict(num_layers=4, mixers=("deltanet", "attention"), gdn_key_heads=2, gdn_value_heads=4,
          gdn_key_dim=16, gdn_value_dim=16), 2),
], ids=["three-blocks", "trinitys-pattern", "no-remat", "local", "every-other-a-deltanet"])
def test_attn_kept_counts_the_cores_whose_forward_kernel_the_backward_pass_leaves_out(fields, kept, monkeypatch):
    model = lm(**fields)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 33), 0, 61)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])
    loss = loss_of(model, tokens)
    counters = telemetry.get_registry().counters
    before = counters["attn.kept"]
    names = kernels_of(loss, params)  # one trace
    assert counters["attn.kept"] - before == kept
    monkeypatch.setattr(transformer, "KEPT_RESIDUALS", ())
    names_whole = kernels_of(loss, params)
    assert len(names_whole) - len(names) == kept
