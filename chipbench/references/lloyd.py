"""Plain reference for the ``kmeans_fit`` kind: seeded data, Lloyd's
algorithm in straightforward float32 ``jax.numpy``, and the comparison.

Imports nothing of the program. Runs where the data lives: every function
takes the mesh and its axis name and works shard by shard (``shard_map``),
in row blocks, so that it needs little memory beside the data.

``products="direct"`` is the reference: distances as sums of squared
differences, cluster sums at ``highest`` precision. ``products="bf16"`` is
the control that the comparison has to fail: the same iteration in the
quadratic form with every matrix product taken once on operands rounded to
bfloat16, the step below the three-pass product the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key from any whole number (the driver's seeds pass 2**31)."""
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(stream))


def mixture_means(seed: int, clusters: int, features: int, spread: float) -> jax.Array:
    return spread * jax.random.normal(seed_key(seed, 0), (clusters, features), F32)


def _mixture_rows(key, means, rows: int) -> jax.Array:
    kz, kn = jax.random.split(key)
    z = jax.random.randint(kz, (rows,), 0, means.shape[0], jnp.int32)
    return means[z] + jax.random.normal(kn, (rows, means.shape[1]), F32)


@functools.partial(jax.jit, static_argnames=("rows_per_shard", "block", "mesh", "axis"))
def _make_mixture(key, means, *, rows_per_shard, block, mesh, axis):
    def shard(key, means):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        keys = jax.random.split(key, rows_per_shard // block)
        x = jax.lax.map(lambda k: _mixture_rows(k, means, block), keys)
        return x.reshape(rows_per_shard, means.shape[1])

    return jax.shard_map(
        shard, mesh=mesh, in_specs=(P(), P()), out_specs=P(axis), check_vma=False
    )(key, means)


def make_mixture(seed, rows_per_shard, features, clusters, spread, block, mesh, axis):
    """(shards * rows_per_shard, features) float32, split by rows over the
    mesh: unit Gaussians around ``clusters`` means drawn with deviation
    ``spread`` in every coordinate, so that neighbours overlap in their tails
    and rows sit near every boundary. Shard ``i`` depends on the seed and ``i`` alone."""
    if rows_per_shard % block:
        raise ValueError("rows_per_shard must be a multiple of the data block")
    means = mixture_means(seed, clusters, features, spread)
    return _make_mixture(
        seed_key(seed, 1), means,
        rows_per_shard=rows_per_shard, block=block, mesh=mesh, axis=axis,
    )


def initial_centres(seed, sets, clusters, features, spread) -> np.ndarray:
    """(sets, clusters, features): for each call one fresh draw from each
    component of the mixture, in the components' order. Heat's
    ``init='random'`` takes data rows too; one from each component keeps two
    centres from starting in one cluster, where Lloyd's iteration splits a
    round cluster along a direction that rounding decides."""
    means = mixture_means(seed, clusters, features, spread)
    noise = jax.random.normal(seed_key(seed, 2), (sets, clusters, features), F32)
    return np.asarray(means[None, :, :] + noise)


def _d2(xb, c, products):
    if products == "direct":
        diff = xb[:, None, :] - c[None, :, :]
        return jnp.sum(diff * diff, axis=-1)
    prod = jnp.matmul(
        xb.astype(jnp.bfloat16), c.astype(jnp.bfloat16).T, preferred_element_type=F32
    )
    x2 = jnp.sum(xb * xb, axis=1, keepdims=True)
    return jnp.maximum(x2 + jnp.sum(c * c, axis=1)[None, :] - 2.0 * prod, 0.0)


def _sums(onehot, xb, products):
    if products == "direct":
        return jnp.matmul(onehot.T, xb, precision=HIGHEST)
    return jnp.matmul(
        onehot.T.astype(jnp.bfloat16), xb.astype(jnp.bfloat16), preferred_element_type=F32
    )


@functools.partial(
    jax.jit, static_argnames=("iters", "block", "products", "mesh", "axis")
)
def _lloyd(x, c0, *, iters, block, products, mesh, axis):
    k = c0.shape[0]
    ids = jnp.arange(k, dtype=jnp.int32)

    def shard(xs, c0):
        xs = xs.reshape(-1, block, xs.shape[1])

        def update(c):
            def add(acc, xb):
                d2 = _d2(xb, c, products)
                lab = jnp.argmin(d2, axis=1).astype(jnp.int32)
                onehot = (lab[:, None] == ids[None, :]).astype(F32)
                return (acc[0] + _sums(onehot, xb, products), acc[1] + onehot.sum(0)), None

            zero = (jnp.zeros_like(c), jnp.zeros((k,), F32))
            (sums, counts), _ = jax.lax.scan(add, zero, xs)
            sums, counts = jax.lax.psum((sums, counts), axis)
            counts = counts[:, None]
            return jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), c)

        c = jax.lax.fori_loop(0, iters, lambda _, c: update(c), c0)

        def assign(xb):
            d2 = _d2(xb, c, products)
            return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.sum(jnp.min(d2, axis=1))

        labels, inertia = jax.lax.map(assign, xs)
        return c, labels.reshape(-1), jax.lax.psum(jnp.sum(inertia), axis)

    return jax.shard_map(
        shard, mesh=mesh, in_specs=(P(axis), P()), out_specs=(P(), P(axis), P()),
        check_vma=False,
    )(x, c0)


def lloyd(x, c0, iters, block, mesh, axis, products="direct"):
    """``iters`` Lloyd iterations from ``c0`` over all rows of ``x``, then one
    assignment pass: (centres (k, d), labels (n,) int32, inertia). An empty
    cluster keeps its centre."""
    return _lloyd(
        x, jnp.asarray(c0, F32), iters=iters, block=block, products=products,
        mesh=mesh, axis=axis,
    )


@jax.jit
def _label_share(a, b):
    return jnp.mean((a.astype(jnp.int32) != b.astype(jnp.int32)).astype(F32))


def gaps(got_centres, got_inertia, want_centres, want_inertia, got_labels=None, want_labels=None):
    """The numbers compared, each a gap of the program's answer from the
    reference's: the worst centre's distance from its reference over the
    mean norm of the reference's centres, the inertia's relative gap, and
    the share of rows labelled differently."""
    got = np.asarray(got_centres, np.float64)
    want = np.asarray(want_centres, np.float64)
    norm = float(np.mean(np.linalg.norm(want, axis=1)))
    out = {
        "centres_gap": float(np.max(np.linalg.norm(got - want, axis=1))) / norm,
        "inertia_gap": abs(float(got_inertia) - float(want_inertia)) / abs(float(want_inertia)),
    }
    if got_labels is not None:
        out["labels_differ_share"] = float(_label_share(got_labels, want_labels))
    return out
