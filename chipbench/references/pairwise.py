"""Plain reference for the ``cdist`` kind: seeded rows, distances as direct
differences in float32, and the comparison.

Imports nothing of the program. ``products="direct"`` is the reference;
``products="bf16"`` is the control that the comparison has to fail: the
quadratic expansion with its one matrix product taken once on operands
rounded to bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

F32 = jnp.float32


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key from any whole number (the driver's seeds pass 2**31)."""
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(stream))


@functools.partial(jax.jit, static_argnames=("rows_per_shard", "features", "mesh", "axis"))
def _make_rows(key, *, rows_per_shard, features, mesh, axis):
    def shard(key):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        return jax.random.normal(key, (rows_per_shard, features), F32)

    return jax.shard_map(
        shard, mesh=mesh, in_specs=P(), out_specs=P(axis), check_vma=False
    )(key)


def make_rows(seed, rows_per_shard, features, mesh, axis):
    """(shards * rows_per_shard, features) standard normal float32, split by
    rows over the mesh."""
    return _make_rows(
        seed_key(seed, 1), rows_per_shard=rows_per_shard, features=features,
        mesh=mesh, axis=axis,
    )


def sample_blocks(seed, rows, block, blocks) -> np.ndarray:
    """Starts of ``blocks`` row blocks of ``block`` rows, drawn from the
    seed; the first row and the last are always among them."""
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, rows - block + 1, size=max(blocks - 2, 0))
    return np.unique(np.concatenate([[0, rows - block], starts])).astype(np.int64)


@functools.partial(jax.jit, static_argnames=("products", "chunk"))
def distances(xs, y, *, products="direct", chunk=4096):
    """(b, n) euclidean distances of the rows ``xs`` from all rows of ``y``,
    by column chunks."""
    n, d = y.shape
    pad = -n % chunk
    yc = jnp.pad(y, ((0, pad), (0, 0))).reshape(-1, chunk, d)

    def one(yb):
        if products == "direct":
            diff = xs[:, None, :] - yb[None, :, :]
            return jnp.sqrt(jnp.sum(diff * diff, axis=-1))
        prod = jnp.matmul(
            xs.astype(jnp.bfloat16), yb.astype(jnp.bfloat16).T, preferred_element_type=F32
        )
        x2 = jnp.sum(xs * xs, axis=1, keepdims=True)
        y2 = jnp.sum(yb * yb, axis=1)[None, :]
        return jnp.sqrt(jnp.maximum(x2 + y2 - 2.0 * prod, 0.0))

    out = jax.lax.map(one, yc)  # (chunks, b, chunk)
    return jnp.moveaxis(out, 0, 1).reshape(xs.shape[0], -1)[:, :n]


@jax.jit
def _d2_gap(got, want, xs, y):
    scale = jnp.sum(xs * xs, axis=1, keepdims=True) + jnp.sum(y * y, axis=1)[None, :]
    gap = jnp.abs(got * got - want * want) / scale
    return jnp.max(gap), jnp.sqrt(jnp.mean(gap * gap))


def gaps(got, want, xs, y):
    """The numbers compared for one block of rows: the widest and the
    root-mean-square gap between the squared distances, each over
    ``|x|^2 + |y|^2``. Squared, because the quadratic form cancels near zero
    distance: on the diagonal an error of 1e-5 in d^2 is 3e-3 in d."""
    worst, rms = _d2_gap(got, want, xs, y)
    return {"d2_gap_max": float(worst), "d2_gap_rms": float(rms)}
