"""The sliding window in the Pallas flash kernels: ``flash_attention(window=)``,
forward, dq and dk/dv (and the fused backward), in the Pallas interpreter on
the CPU against the masked XLA form: position ``t`` sees ``t - window < j <= t``.

Tolerances. float32: the kernel and the dense form sum the same products in
another order: 2e-5 absolute on outputs of order 1, 5e-5 on gradients (sums
over up to 384 queries). bfloat16: operands, probabilities and the output round
to 8 bits (2^-9 relative each): 3e-2 on outputs, 1e-1 on gradients whose
entries reach 2 to 3.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import trinity_plain
from heat_tpu.parallel import flash_attention, local_attention
from heat_tpu.parallel.pallas_attention import window_grid


def dense(q, k, v, window, kv_valid=None):
    """The masked XLA form: a full score matrix under the mask, float32."""
    t, tk, d = q.shape[1], k.shape[1], q.shape[-1]
    k, v = (jnp.repeat(a, q.shape[2] // a.shape[2], axis=2).astype(jnp.float32) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k, precision="highest") / np.sqrt(d)
    q_pos, k_pos = jnp.arange(t)[:, None], jnp.arange(tk)[None, :]
    mask = k_pos <= q_pos
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    if kv_valid is not None:
        mask = mask & (k_pos < kv_valid)
    p = jnp.where(mask, jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def inputs(t, heads, kv_heads, d, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (1, t, heads, d), dtype)
    k = jax.random.normal(keys[1], (1, t, kv_heads, d), dtype)
    v = jax.random.normal(keys[2], (1, t, kv_heads, d), dtype)
    return q, k, v, jax.random.normal(keys[3], (1, t, heads, d), jnp.float32)


def both(attend, want, q, k, v, weights):
    """Outputs and dq, dk, dv of ``attend`` and of ``want``, float32."""
    def run(f):
        out = f(q, k, v)
        grads = jax.grad(lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * weights), argnums=(0, 1, 2))(q, k, v)
        return [np.asarray(a.astype(jnp.float32)) for a in (out,) + grads]

    return run(attend), run(want)


# blocks that divide the windows 64 and 384 and not 24, 100 or T + 5; one pair wider than it is tall, one taller
BLOCKS = [(32, 32), (32, 64), (64, 32)]


@pytest.mark.parametrize("blocks", BLOCKS, ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("window", [1, 24, 64, "T", "T+5"])
@pytest.mark.parametrize("t", [64, 100, 384])
def test_the_window_kernels_against_the_masked_form(t, window, blocks):
    window = {"T": t, "T+5": t + 5}.get(window, window)
    q, k, v, weights = inputs(t, 2, 2, 16, jnp.float32)
    attend = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window, block_q=blocks[0], block_k=blocks[1]
    )
    got, want = both(attend, lambda q, k, v: dense(q, k, v, window), q, k, v, weights)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("bwd", ["two_pass", "fused"])
@pytest.mark.parametrize("kv_valid", [None, 300])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_grouped_heads_padding_and_both_backward_forms(dtype, kv_valid, bwd):
    """8 query heads on 1 key-value head read by index, keys past ``kv_valid``
    masked, T no multiple of the blocks, a window the blocks do not divide."""
    t, window = 340, 100
    q, k, v, weights = inputs(t, 8, 1, 16, dtype, seed=3)
    attend = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window, block_q=64, block_k=32, kv_valid=kv_valid, bwd_impl=bwd
    )
    got, want = both(attend, lambda q, k, v: dense(q, k, v, window, kv_valid), q, k, v, weights)
    out_tol, grad_tol = (2e-5, 1e-4) if dtype == jnp.float32 else (3e-2, 1e-1)
    np.testing.assert_allclose(got[0], want[0], atol=out_tol, rtol=out_tol)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(g, w, atol=grad_tol, rtol=grad_tol)


@pytest.mark.parametrize("window", [24, 100])
def test_local_attention_takes_the_same_window(window):
    q, k, v, weights = inputs(200, 4, 4, 16, jnp.float32, seed=5)
    attend = lambda q, k, v: local_attention(q, k, v, causal=True, window=window, block_size=64)  # noqa: E731
    got, want = both(attend, lambda q, k, v: dense(q, k, v, window), q, k, v, weights)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("impl", ["flash", "local"])
def test_the_edge_probe_fails_a_window_off_by_one_in_either_direction(impl):
    """The keys exactly ``window - 1`` and ``window`` before a query carry its
    largest scores (``trinity_plain.edge_probe``): the right mask keeps the
    first and drops the second. The kernels agree with the masked form at 2e-5;
    the masked form one short or one long differs from it by over 10 x that on
    every array, so an edge off by one in either direction cannot pass."""
    t, window, tol = 200, 48, 2e-5
    q, k, v = trinity_plain.edge_probe(11, t, 4, 2, 16, window)
    weights = inputs(t, 4, 2, 16, jnp.float32, seed=9)[3]
    if impl == "flash":
        attend = lambda q, k, v: flash_attention(q, k, v, causal=True, window=window, block_q=32, block_k=32)  # noqa: E731
    else:
        attend = lambda q, k, v: local_attention(  # noqa: E731
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), causal=True, window=window, block_size=64
        )
    got, want = both(attend, lambda q, k, v: dense(q, k, v, window), q, k, v, weights)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=tol, rtol=tol)
    for wrong in (window - 1, window + 1):
        off = both(lambda q, k, v: dense(q, k, v, wrong), lambda q, k, v: dense(q, k, v, window), q, k, v, weights)[0]
        for o, w in zip(off, want):
            assert np.max(np.abs(o - w)) > 10 * tol * max(1.0, float(np.max(np.abs(w))))
        # and by a large share of the output itself: a half (one long) or all of it (one short)
        assert trinity_plain.rms_gap(off[0], want[0]) > 0.3


def _pallas_calls(jaxpr, out):
    def subjaxprs(v):
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr"):
            yield from subjaxprs(v.jaxpr)
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from subjaxprs(x)

    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e)
            continue
        for v in e.params.values():
            for sub in subjaxprs(v):
                _pallas_calls(sub, out)
    return out


def _lowered(window, heads=8, kv_heads=1, t=384, d=16, dtype=jnp.float32, bwd="two_pass", causal=True, **blocks):
    q = jax.ShapeDtypeStruct((1, t, heads, d), dtype)
    k = jax.ShapeDtypeStruct((1, t, kv_heads, d), dtype)

    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, window=window, bwd_impl=bwd, interpret=False, **blocks
        ).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    return jax.make_jaxpr(f)(q, k, k)


def test_a_windowed_grid_visits_the_bands_blocks_only():
    """Read from the lowered ``pallas_call``s: with T 384, window 64 and
    blocks of 32 the full form's key axis has 12 blocks; a query block's band
    is 3 of them (95 positions), a key block's band of query blocks 3 as well,
    followed by each of the group's 8 heads in turn."""
    calls = {e.params["name"]: e.params["grid_mapping"].grid for e in _pallas_calls(
        _lowered(64, block_q=32, block_k=32).jaxpr, []
    )}
    assert calls == {"swa_fwd": (1, 8, 12, 3), "swa_bwd_dq": (1, 8, 12, 3), "swa_bwd_dkv": (1, 1, 12, 8 * 3)}
    full = {e.params["name"]: e.params["grid_mapping"].grid for e in _pallas_calls(
        _lowered(None, block_q=32, block_k=32).jaxpr, []
    )}
    assert full == {"flash_fwd": (1, 8, 12, 12), "flash_bwd_dq": (1, 8, 12, 12), "flash_bwd_dkv": (1, 1, 12, 8 * 12)}
    # what the shape gives: one backward kernel, the group's heads in turn, a query block's band of keys
    fused = {e.params["name"]: e.params["grid_mapping"].grid for e in _pallas_calls(
        _lowered(64, bwd="auto", block_q=32, block_k=32).jaxpr, []
    )}
    assert fused == {"swa_fwd": (1, 8, 12, 3), "swa_bwd_fused": (1, 1, 8, 12, 3)}
    # the band is ceil((block_q + window - 1) / block_k) + 1 blocks at most, whatever the blocks
    for bq, bk, window in [(32, 32, 24), (32, 64, 100), (64, 32, 100), (128, 128, 384)]:
        steps = _pallas_calls(_lowered(window, block_q=bq, block_k=bk).jaxpr, [])[0].params["grid_mapping"].grid[3]
        assert steps <= -(-(bq + window - 1) // bk) + 1 and steps <= -(-384 // bk)


def test_the_grid_covers_the_band_and_little_more():
    # at the sequence's start the band is shorter than the grid's axis: those steps read no new block
    assert window_grid(384, 384, 64, 32, 32) == (36, 33)
    visited, live = window_grid(16384, 16384, 2048)
    assert live <= visited <= 1.1 * live
    assert window_grid(384, 384, 1, 32, 32) == (12, 12)  # a window of one: the diagonal blocks


# sha256 of flash_attention's forward and backward with no window. "kernels": the four ``pallas_call`` equations
# alone (grids, index maps, kernel bodies); "whole": the jaxpr round them (PR 34's forward rule names ``out`` and the
# log-sum-exp's column, its backward prologue broadcasts the column back to the kernels' lanes). The form without
# ``causal`` is still the one recorded from 4f3dc3a (PR 32; the same at 58dc9ba, PR 31, but for PR 34's "whole"):
# every block visited, one body. The three causal forms were re-pinned by PR 38, which gave them the band's grid
# (the key axis ends at a row's diagonal, blocks wholly under it take no mask: ``tests/test_flash_causal_band.py``
# holds them to the parent's values and walks their index maps); their kernels keep the names ``flash_*``. The fused
# backward was re-pinned by PR 42, which gave it the key band and made dK, dV its resident blocks
PARENT_FORM = {
    (16, 16, 4096, 128, "bfloat16", "two_pass", True): {
        "kernels": "d4c35e2b70175ac8901d1a8054fca14463308bd2fde9368f886e2b01c1f3dd67",
        "whole": "7697a068e45e7c2d4e5f07c64619df499149bc10d3a50bd732177dab36755870",
    },
    (16, 2, 8192, 256, "bfloat16", "two_pass", True): {
        "kernels": "1a9fc54602dca67a91391cd54fe6220c628b82569d1071df2dd999b5af4195c9",
        "whole": "2c7c05b92d1fa87d5718f49acda7a4e807cab5267551d62a248ad5ca86ce0a95",
    },
    (4, 1, 384, 64, "float32", "fused", True): {
        "kernels": "41267c5765f3f6d1dea654d1ce3c306990acb106ee0e6fcf27e44818ec0b1d8a",
        "whole": "9e5ed321290bd45c46e4dae00283ab46b5eef2832a870539a8902bbaa63a7e44",
    },
    (4, 2, 300, 128, "float32", "two_pass", False): {
        "kernels": "79dc22c92d7e9c01f7b1f6822cd83e3bb4838a07b02036ec0c5f96ce4e9f6a3a",
        "whole": "150ccbd3d363c0acfe749e9f0aca4c8512d8006b576c0dbab0a36b2fda1d6574",
    },
}


@pytest.mark.parametrize("part", ["kernels", "whole"])
@pytest.mark.parametrize("form", sorted(PARENT_FORM), ids=lambda f: f"{f[0]}on{f[1]}x{f[2]}x{f[3]}-{f[5]}")
def test_without_a_window_the_kernels_lower_to_the_parents_form(form, part):
    """Without ``causal``: the parent's form of PR 32, untouched. With it: the
    band's grid as PR 38 recorded it; a change of either is made on purpose."""
    heads, kv_heads, t, d, dtype, bwd, causal = form
    lowered = _lowered(None, heads, kv_heads, t, d, jnp.dtype(dtype), bwd, causal)
    text = str(lowered) if part == "whole" else "\n".join(str(e) for e in _pallas_calls(lowered.jaxpr, []))
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_FORM[form][part]


def test_a_window_needs_causal_and_a_whole_number_of_positions():
    q, k, v, _ = inputs(64, 2, 2, 16, jnp.float32)
    for attend in (flash_attention, local_attention):
        with pytest.raises(ValueError, match="window"):
            attend(q, k, v, causal=False, window=8)
        with pytest.raises(ValueError, match="window"):
            attend(q, k, v, causal=True, window=0)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_the_sequence_parallel_forms_refuse_a_window(impl):
    from heat_tpu.nn import MultiHeadAttention

    layer = MultiHeadAttention(2, attn_impl=impl, window=8)
    with pytest.raises(ValueError, match="sliding window"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 8)))
