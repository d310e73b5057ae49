"""Kind ``cdist``: one call is ``ht.spatial.cdist(X, quadratic_expansion=...)``
of X against itself on the array that set-up left on the device.
"""

from __future__ import annotations

import jax


class State:
    def __init__(self, config, comm, seed, reference):
        import heat_tpu as ht

        self.config, self.comm, self.seed, self.ref = config, comm, seed, reference
        if config["rows"] % comm.size:
            raise ValueError("rows must divide over the cell's chips")
        x = reference.make_rows(
            seed, config["rows"] // comm.size, config["features"], comm.mesh, comm.axis_name
        )
        self.x = ht.array(x, split=0, comm=comm)


def setup(config, comm, seed, reference):
    return State(config, comm, seed, reference)


def items_per_call(config, chips):
    return config["rows"]


def call(state, i):
    import heat_tpu as ht

    return ht.spatial.cdist(state.x, quadratic_expansion=state.config["quadratic_expansion"])


def outputs(d):
    return d.larray


def summary(d):
    return None


def _gaps_by_block(state, got_of):
    """For each seeded row block: the gaps of ``got_of(rows, x, start)``
    from the reference's distances of those rows to all of X."""
    chk, x = state.config["check"], state.x.larray
    block = min(chk["block_rows"], state.config["rows"])
    starts = state.ref.sample_blocks(state.seed, state.config["rows"], block, chk["blocks"])
    out = []
    for s in starts:
        xs = jax.lax.dynamic_slice_in_dim(x, int(s), block, axis=0)
        out.append((int(s), state.ref.gaps(got_of(xs, x, int(s)), state.ref.distances(xs, x), xs, x)))
    return out


def check(state, calls, last):
    """Compare a seeded sample of row blocks of the last call's result (the
    one still held; every call computes the same matrix) against all
    columns. Returns one row of numbers per block."""
    block = min(state.config["check"]["block_rows"], state.config["rows"])
    return _gaps_by_block(
        state, lambda xs, x, s: jax.lax.dynamic_slice_in_dim(last.larray, s, block, axis=0)
    )


def control(state, i):
    """The control's worst numbers over the same blocks."""
    worst = {}
    for _, row in _gaps_by_block(state, lambda xs, x, s: state.ref.distances(xs, x, products="bf16")):
        for name, v in row.items():
            worst[name] = max(worst.get(name, 0.0), v)
    return worst
