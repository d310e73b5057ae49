"""The full causal form of the flash kernels on the band's grid
(``flash_attention(causal=True)`` with no window): a causal mask is a band whose
window is the whole sequence, so the grid's sequential axis covers the blocks up
to the diagonal alone, a step past it names the block before it again (no copy)
and a block wholly under the diagonal takes no mask.

Sameness first: ``window=None`` and a window that sees everything
(``t_q + t_k``) at the same tiles are one function. float32 to 1e-6 (under the
interpreter the two bodies differ by 2e-7 at worst where XLA:CPU compiles an
unmasked block's exponentials otherwise), bfloat16 to one bfloat16 step of the
array's largest entry. The test needs nothing of the band's causal form: it
passed at fbb99b0 (PR 35), where the full form still walked every block.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat_tpu import telemetry
from heat_tpu.parallel import flash_attention
from heat_tpu.parallel.pallas_attention import _key_band, _query_band, causal_grid
from tests.test_flash_window import _pallas_calls

# heads, key-value heads, t_q, t_k, d, dtype, block_q, block_k, backward, kv_valid
SAME = {
    # rows with dead, edge and inside blocks: 8 query blocks on 4 key blocks
    "grouped-8on2-T2048-bf16": (8, 2, 2048, 2048, 64, jnp.bfloat16, 256, 512, "two_pass", None),
    "D256-bf16": (4, 2, 512, 512, 256, jnp.bfloat16, 128, 256, "two_pass", None),
    "fused-4on1-T384-f32": (4, 1, 384, 384, 64, jnp.float32, 128, 128, "fused", None),
    "ragged-T300-valid257-f32": (4, 2, 300, 300, 32, jnp.float32, 64, 128, "two_pass", 257),
    "tq256-tk384-f32": (2, 2, 256, 384, 32, jnp.float32, 64, 128, "two_pass", None),
    "tq384-tk256-fused-f32": (2, 1, 384, 256, 32, jnp.float32, 128, 64, "fused", None),
}


def _out_and_grads(window, case):
    heads, kv_heads, t_q, t_k, d, dtype, block_q, block_k, bwd, kv_valid = case
    keys = jax.random.split(jax.random.PRNGKey(38), 4)
    q = jax.random.normal(keys[0], (1, t_q, heads, d), dtype)
    k = jax.random.normal(keys[1], (1, t_k, kv_heads, d), dtype)
    v = jax.random.normal(keys[2], (1, t_k, kv_heads, d), dtype)
    weights = jax.random.normal(keys[3], (1, t_q, heads, d), jnp.float32)

    def attend(q, k, v):
        return flash_attention(
            q, k, v, causal=True, window=window, block_q=block_q, block_k=block_k, bwd_impl=bwd, kv_valid=kv_valid
        )

    out, pull = jax.vjp(attend, q, k, v)
    return [np.asarray(a.astype(jnp.float32)) for a in (out,) + pull(weights.astype(dtype))]


@pytest.mark.parametrize("name", sorted(SAME))
def test_the_full_form_is_the_band_at_a_window_that_sees_everything(name):
    case = SAME[name]
    full, band = _out_and_grads(None, case), _out_and_grads(case[2] + case[3], case)
    for what, a, b in zip(("out", "dq", "dk", "dv"), full, band):
        assert np.all(np.isfinite(a)), what
        if case[5] == jnp.float32:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6, err_msg=what)
        else:  # one step of bfloat16 (8 bits) at the largest entry
            step = 2.0 ** (np.floor(np.log2(np.max(np.abs(b)))) - 7)
            np.testing.assert_allclose(a, b, rtol=0, atol=step, err_msg=what)


# -- the grid, as data -----------------------------------------------------------------------

# t_q, t_k, block_q, block_k, query heads, key-value heads: the three training cells' full layers at their tuned
# tiles (512 x 1,024) and two forms whose queries and keys differ in number
GRIDS = {
    "trinity-16k": (16384, 16384, 512, 1024, 32, 4),
    "qwen3next-8k": (8192, 8192, 512, 1024, 16, 2),
    "olmoe-4k": (4096, 4096, 512, 1024, 16, 16),
    "tq256-tk384": (256, 384, 64, 128, 2, 1),
    "tq384-tk256": (384, 256, 128, 64, 4, 2),
}
LIVE = {"trinity-16k": 272, "qwen3next-8k": 72, "olmoe-4k": 20}  # key blocks a head that hold a visible pair


def _calls(t_q, t_k, block_q, block_k, heads, kv_heads, window=None, d=128, dtype=jnp.bfloat16, bwd="two_pass"):
    """The ``pallas_call`` equations of forward and backward, by kernel name
    (lowered for the chip, not run)."""
    q = jax.ShapeDtypeStruct((1, t_q, heads, d), dtype)
    k = jax.ShapeDtypeStruct((1, t_k, kv_heads, d), dtype)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window, bwd_impl=bwd, interpret=False, block_q=block_q, block_k=block_k
        ).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    return {e.params["name"]: e for e in _pallas_calls(jax.make_jaxpr(f)(q, k, k).jaxpr, [])}


def _walk(call, operand):
    """One pass over the grid's axes past the first two in the order the
    pipeline takes them (first batch, first head of axis 1): the ``(head,
    block)`` that the operand's index map names at each step, as an array
    ``(*axes, 2)`` (rows x steps; for the fused backward group x rows x steps)."""
    mapping = call.params["grid_mapping"]
    index_map = mapping.block_mappings[operand].index_map_jaxpr
    at = [a.ravel().astype(np.int32) for a in np.meshgrid(*map(np.arange, mapping.grid[2:]), indexing="ij")]
    zero = np.zeros_like(at[0])
    index = jax.vmap(lambda *at: jax.core.eval_jaxpr(index_map.jaxpr, index_map.consts, *at))(zero, zero, *at)
    return np.stack([np.asarray(index[1]), np.asarray(index[2])], axis=-1).reshape(*mapping.grid[2:], 2)


def _copied(walk):
    """Of each row the steps that name another block than the step before
    them, the row's first among them: what the pipeline copies."""
    moved = np.any(walk[:, 1:] != walk[:, :-1], axis=-1)
    return [[tuple(row[0])] + [tuple(b) for b, m in zip(row[1:], moves) if m] for row, moves in zip(walk, moved)]


@pytest.mark.parametrize("name", sorted(GRIDS))
def test_the_causal_grid_copies_the_live_blocks_and_no_other(name):
    """Walked through the index maps of the lowered ``pallas_call``s: forward
    and dq copy a query block's key blocks up to its diagonal, dk/dv a key
    block's query blocks from its diagonal on, once for each head of the
    group; the steps past them name the last block again."""
    t_q, t_k, block_q, block_k, heads, kv_heads = GRIDS[name]
    # the blocks that hold a pair some query sees, by position and not by the band's arithmetic
    live = [
        (iq, ik) for iq in range(t_q // block_q) for ik in range(t_k // block_k) if ik * block_k <= iq * block_q + block_q - 1
    ]
    assert len(live) == LIVE.get(name, len(live))
    calls = _calls(*GRIDS[name])
    assert sorted(calls) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    for kernel in ("flash_fwd", "flash_bwd_dq"):  # K and V: operands 1 and 2
        for operand in (1, 2):
            copied = _copied(_walk(calls[kernel], operand))
            assert [(iq, ik) for iq, row in enumerate(copied) for _, ik in row] == live, (kernel, operand)
    # a key block past the last query (more keys than queries) has no live pair: its one step reads the last query
    # block, wholly masked
    beyond = [(t_q // block_q - 1, ik) for ik in range(t_k // block_k) if ik * block_k >= t_q]
    want = sorted((ik, g, iq) for g in range(heads // kv_heads) for iq, ik in live + beyond)
    for operand in (0, 3, 4, 5):  # Q, dO, the log-sum-exp and D
        copied = _copied(_walk(calls["flash_bwd_dkv"], operand))
        assert sorted((ik, g, iq) for ik, row in enumerate(copied) for g, iq in row) == want, operand
    keys = _key_band(True, None, t_q, t_k, block_q, block_k)
    assert (keys.visited, keys.streamed, keys.live) == causal_grid(t_q, t_k, block_q, block_k)
    assert keys.streamed == keys.live == len(live) and keys.visited == calls["flash_fwd"].params["grid_mapping"].grid[3] * (t_q // block_q)
    queries = _query_band(True, None, t_q, t_k, block_q, block_k)
    assert queries.streamed == len(live) + len(beyond) and queries.steps * (heads // kv_heads) == calls["flash_bwd_dkv"].params["grid_mapping"].grid[3]
    if name in LIVE:
        assert causal_grid(t_q, t_k)[1:] == (LIVE[name], LIVE[name])  # the tuned tiles are the default


@pytest.mark.parametrize("name", sorted(GRIDS))
def test_the_rule_takes_one_backward_kernel_on_the_key_band(name):
    """What the shape gives (``bwd_impl="auto"``, the default): the fused
    kernel, whose grid is a key-value head's query heads in turn, their query
    blocks, and a query block's key blocks up to its diagonal: K and V are
    copied as the forward copies them, Q, dO and the two columns once a query
    block, and dk, dv stay one block a key-value head."""
    t_q, t_k, block_q, block_k, heads, kv_heads = GRIDS[name]
    calls = _calls(*GRIDS[name], bwd="auto")
    assert sorted(calls) == ["flash_bwd_fused", "flash_fwd"]
    fused, group, rows = calls["flash_bwd_fused"], heads // kv_heads, t_q // block_q
    keys = _key_band(True, None, t_q, t_k, block_q, block_k)
    assert fused.params["grid_mapping"].grid == (1, kv_heads, group, rows, keys.steps)
    live = [(iq, ik) for iq in range(rows) for ik in range(t_k // block_k) if ik * block_k <= iq * block_q + block_q - 1]
    for operand in (1, 2):  # K and V, of the group's one head
        copied = _copied(_walk(fused, operand).reshape(group * rows, keys.steps, 2))
        assert [(i % rows, ik) for i, row in enumerate(copied) for _, ik in row] == live * group, operand
        assert {head for row in copied for head, _ in row} == {0}
    for operand in (0, 3, 4, 5, 6):  # Q, dO, the log-sum-exp, D and dq: a query head's block, the same at every step
        walk = _walk(fused, operand)
        assert [row == [(g, iq)] for g in range(group) for iq in range(rows) for row in [_copied(walk[g])[iq]]] == [True] * (group * rows)
    for operand in (7, 8):  # dk, dv
        assert not _walk(fused, operand).any()


# sha256 of the windowed form's ``pallas_call`` equations (grids, index maps, bodies) at fbb99b0 (PR 35), before the
# full form took the band's grid: the windowed kernels are this change's control (the fused form as PR 42 left it,
# on the key band with dK, dV resident)
WINDOWED_AT_THE_PARENT = {
    (16384, 16384, 1024, 1024, 32, 4, 2048, 128, "bfloat16", "two_pass"): "249f050263728dda680bbd01a77389bd36c85b27bfb9ba6a24eda846bfe70d74",
    (384, 384, 64, 32, 8, 1, 100, 16, "float32", "fused"): "757d1dea9e5f638cae722666122ff51e51ea80be6ca83e0721d13166b0df459b",
    (300, 300, 32, 64, 4, 2, 24, 16, "float32", "two_pass"): "9ccbb8b31614fd802f4287e9280fd384e9104195f4c5471bd80fd9a232e01353",
}


@pytest.mark.parametrize("form", sorted(WINDOWED_AT_THE_PARENT), ids=lambda f: f"{f[4]}on{f[5]}x{f[0]}-w{f[6]}-{f[9]}")
def test_with_a_window_the_kernels_lower_to_the_parents(form):
    calls = _calls(*form[:8], jnp.dtype(form[8]), form[9])
    assert all(name.startswith("swa_") for name in calls)
    text = "\n".join(str(calls[name]) for name in sorted(calls))
    assert hashlib.sha256(text.encode()).hexdigest() == WINDOWED_AT_THE_PARENT[form]


def test_a_model_counts_what_its_full_causal_layers_copy():
    from heat_tpu.nn import TransformerLM

    registry = telemetry.get_registry()
    before = dict(registry.counters)
    model = TransformerLM(64, 32, 4, 2, max_len=256, attn_impl="flash", block_size=64)
    jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32))
    added = {k: v - before.get(k, 0.0) for k, v in registry.counters.items() if v != before.get(k, 0.0)}
    assert added["attn.full.kernel"] >= 2
    # four query blocks of 64 on four key blocks: 1 + 2 + 3 + 4 a head and layer
    assert added["attn.full.blocks_streamed"] == added["attn.full.blocks_live"] == 10 * added["attn.full.kernel"]
    assert not [k for k in added if k.startswith("attn.window")]
