"""The work round the held experts goes by the rows that are live:
``nn/moe.py::_held_experts`` gathers a window's rows and sums them back into
their tokens a block at a time, as many blocks as hold a held row. Here against
the uncut layer's held part written out plainly (every assignment a row, the
ones on other experts selected out), values and every gradient, at the live
counts where a block or a window ends; and once more with grouped products that
leave NaN in every row past their last group, forward and backward, as the chip
leaves those rows as they lay in memory (a CPU zeroes them and would hide a read).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat_tpu.nn import moe

N, K, E, FIRST, HELD, D, FF = 128, 3, 32, 4, 4, 32, 16
EVEN = N * K * HELD // E  # 48 of the 384 assignments
BLOCK = -(-EVEN // (8 * moe.LIVE_BLOCKS_A_SHARE)) * 8  # 16


def windows(window):
    """``(first window's rows, a further one's)`` as ``_held_experts`` cuts them."""
    bound = min(N * K, int(np.ceil(window * EVEN / 8)) * 8)
    return bound, min(N * K - bound, -(-EVEN // 8) * 8)


def routing(live, seed):
    """Experts of the ``N * K`` assignments, ``live`` of them on the held
    experts, no token with one expert twice; and weights for them."""
    rng = np.random.default_rng(seed)
    others = np.setdiff1d(np.arange(E), np.arange(FIRST, FIRST + HELD))
    flat = np.stack([rng.permutation(others)[:K] for _ in range(N)])
    picked = np.zeros(N * K, bool)
    picked[rng.permutation(N * K)[:live]] = True
    for t, row in enumerate(picked.reshape(N, K)):
        flat[t, row] = FIRST + rng.permutation(HELD)[:row.sum()]
    return jnp.asarray(flat.reshape(N * K), jnp.int32), jnp.asarray(rng.uniform(0.1, 1.0, N * K), jnp.float32)


def operands(seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    xt = jax.random.normal(keys[0], (N, D), jnp.float32)
    experts = tuple(
        jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[1])
        for key, shape in zip(keys[1:], [(HELD, D, FF), (HELD, D, FF), (HELD, FF, D)])
    )
    return xt, experts


def plain(xt, experts, weights, flat):
    """The held part of the uncut layer: every assignment a row."""
    local = flat - FIRST
    mine = (local >= 0) & (local < HELD)
    w_gate, w_up, w_down = (w[jnp.clip(local, 0, HELD - 1)] for w in experts)
    rows = jnp.repeat(xt, K, axis=0)
    hidden = jax.nn.silu(jnp.einsum("ad,adf->af", rows, w_gate)) * jnp.einsum("ad,adf->af", rows, w_up)
    y = jnp.einsum("af,afd->ad", hidden, w_down)
    return jnp.where(mine[:, None], weights[:, None] * y, 0).reshape(N, K, D).sum(axis=1)


def ours(window, xt, experts, weights, flat):
    counts = jnp.sum(flat[:, None] == jnp.arange(FIRST, FIRST + HELD)[None, :], axis=0, dtype=jnp.int32)
    return moe._held_experts(xt, flat, weights, counts, experts, FIRST, E, K, jnp.float32, window)


def poisoned(real):
    """``jax.lax.ragged_dot`` that leaves NaN in every row past its last
    group: in its result, and in the gradient of its rows."""

    def poison(a, sizes):
        return jnp.where(jnp.arange(a.shape[0])[:, None] < jnp.sum(sizes), a, jnp.nan)

    def ragged_dot(lhs, rhs, group_sizes, **kw):
        @jax.custom_vjp
        def f(lhs, rhs, sizes):
            return poison(real(lhs, rhs, sizes, **kw), sizes)

        def fwd(lhs, rhs, sizes):
            y, transpose = jax.vjp(lambda a, b: real(a, b, sizes, **kw), lhs, rhs)
            return poison(y, sizes), (transpose, sizes)

        def bwd(res, g):
            transpose, sizes = res
            d_lhs, d_rhs = transpose(g)
            return poison(d_lhs, sizes), d_rhs, None

        f.defvjp(fwd, bwd)
        return f(lhs, rhs, group_sizes)

    return ragged_dot


def close(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() <= 2e-5 * max(np.abs(want).max(), 1e-3)


CASES = [  # a first window of 4 even shares and longer goes by blocks, one of 2 whole
    (4.0, 0), (4.0, 1), (4.0, BLOCK - 1), (4.0, BLOCK), (4.0, BLOCK + 1), (4.0, 4 * EVEN), (4.0, 4 * EVEN + 1),
    (4.0, 5 * EVEN + 5), (4.125, 4 * EVEN + 5), (4.125, 200), (8.0, N * K),
    (2.0, 0), (2.0, EVEN + 1), (2.0, 2 * EVEN), (2.0, 3 * EVEN + 5),
]


@pytest.mark.parametrize("window,live", CASES, ids=[f"window{w}-live{n}" for w, n in CASES])
def test_the_held_part_at_live_counts_where_a_block_or_a_window_ends(window, live, monkeypatch):
    flat, weights = routing(live, seed=live)
    xt, experts = operands(seed=live + 1)
    bound, more = windows(window)
    if (window, live) == (4.125, 200):
        assert live == bound and bound % BLOCK  # a window that is no whole number of blocks, full

    def run(f):
        def scalar(xt, experts, weights):
            out = f(xt, experts, weights, flat)
            out, counted = (out[0], out[1:]) if isinstance(out, tuple) else (out, ())
            return jnp.sum(out * jnp.cos(out)), (out, counted)

        with jax.default_matmul_precision("highest"):
            (_, (out, counted)), grads = jax.value_and_grad(scalar, argnums=(0, 1, 2), has_aux=True)(xt, experts, weights)
        return out, counted, grads

    want, _, want_grads = run(plain)
    got, (computed, moved), got_grads = run(functools.partial(ours, window))
    assert int(computed) == live  # every held assignment, none dropped
    in_windows = [min(live, bound)] + [min(max(live - bound - j * more, 0), more) for j in range(8) if more]
    assert sum(in_windows) == live
    if bound > moe.BLOCKS_FROM_SHARES * EVEN:
        assert int(moved) == sum(-(-rows // BLOCK) * BLOCK for rows in in_windows)
    else:  # whole windows: the first, and every further one that holds a row
        assert int(moved) == bound + more * sum(rows > 0 for rows in in_windows[1:])
    assert close(got, want)
    names = ("tokens", ("w_gate", "w_up", "w_down"), "routing weights")
    for name, g, w in zip(names, got_grads, want_grads):
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            assert close(a, b), name

    # the rows past a grouped product's last group hold anything: nothing reads them
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned(jax.lax.ragged_dot))
    again, counted, again_grads = run(functools.partial(ours, window))
    assert [int(c) for c in counted] == [int(computed), int(moved)]
    for a, b in zip(jax.tree.leaves((again, again_grads)), jax.tree.leaves((got, got_grads))):
        assert np.isfinite(np.asarray(a)).all() and np.array_equal(np.asarray(a), np.asarray(b))


def test_the_poison_is_seen_by_a_sum_that_reads_the_whole_window(monkeypatch):
    """The control of the test above: with the parent's sum over the window's
    whole length in place of the one by live blocks, the same NaN rows reach
    the result unless they are selected out."""
    flat, weights = routing(BLOCK + 1, seed=3)
    xt, experts = operands(seed=4)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned(jax.lax.ragged_dot))
    monkeypatch.setattr(
        moe, "_sum_rows",
        lambda block, start, y, w, tokens, live: start + jax.ops.segment_sum(w[:, None] * y, tokens, num_segments=N),
    )
    assert not np.isfinite(np.asarray(ours(4.0, xt, experts, weights, flat)[0])).all()
