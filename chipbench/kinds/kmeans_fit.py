"""Kind ``kmeans_fit``: one call is ``ht.cluster.KMeans(...).fit(X)`` on the
array that set-up left on the device, as a user builds it: a new estimator
for every fit, initial centres passed as a DNDarray.
"""

from __future__ import annotations

import numpy as np


class State:
    def __init__(self, config, comm, seed, reference):
        import heat_tpu as ht

        self.config, self.comm, self.seed, self.ref = config, comm, seed, reference
        data = config["data"]
        self.k, self.d = config["n_clusters"], config["features"]
        x = reference.make_mixture(
            seed, (config["rows"] // comm.size), self.d, self.k, data["spread"],
            min(data["block_rows"], (config["rows"] // comm.size)), comm.mesh, comm.axis_name,
        )
        self.x = ht.array(x, split=0, comm=comm)
        self.inits = reference.initial_centres(
            seed, data["init_sets"], self.k, self.d, data["spread"]
        )

    def init_of(self, i):
        return self.inits[i % len(self.inits)]


def setup(config, comm, seed, reference):
    return State(config, comm, seed, reference)


def items_per_call(config, chips):
    return config["rows"]


def call(state, i):
    import heat_tpu as ht

    c = state.config
    km = ht.cluster.KMeans(
        n_clusters=c["n_clusters"],
        init=ht.array(state.init_of(i), comm=state.comm),
        max_iter=c["max_iter"],
        tol=c["tol"],
    )
    return km.fit(state.x)


def outputs(km):
    """What the user reads: centres and labels (inertia_ and n_iter_ are
    host numbers by the time ``fit`` returns)."""
    return km.cluster_centers_.larray, km.labels_.larray


def summary(km):
    return {
        "centres": np.asarray(km.cluster_centers_.larray),
        "inertia": km.inertia_,
        "n_iter": km.n_iter_,
    }


def _reference_fit(state, i, products="direct"):
    c = state.config
    block = min(c["data"]["block_rows"], (c["rows"] // state.comm.size))
    return state.ref.lloyd(
        state.x.larray, state.init_of(i), c["max_iter"], block,
        state.comm.mesh, state.comm.axis_name, products,
    )


def check(state, calls, last):
    """Compare the last call in full (labels too: its result is the one
    still held) and a seeded sample of the earlier ones by their centres,
    inertia and iteration count. Returns one row of numbers per call
    compared."""
    rng = np.random.default_rng(state.seed)
    earlier = [c for c in calls[:-1] if c.error is None]
    n_more = min(state.config["check"]["calls"] - 1, len(earlier))
    picked = [earlier[j] for j in sorted(rng.choice(len(earlier), n_more, replace=False))]
    rows = []
    for c in picked + [calls[-1]]:
        want_c, want_lab, want_inertia = _reference_fit(state, c.index)
        got_lab = last.labels_.larray if c is calls[-1] else None
        row = state.ref.gaps(
            c.summary["centres"], c.summary["inertia"], want_c, want_inertia, got_lab, want_lab
        )
        row["iterations_gap"] = abs(c.summary["n_iter"] - state.config["max_iter"])
        rows.append((c.index, row))
    return rows


def control(state, i):
    """The control's answer to call ``i`` against the reference's: the same
    numbers as ``check`` gives for the program."""
    want_c, want_lab, want_inertia = _reference_fit(state, i)
    got_c, got_lab, got_inertia = _reference_fit(state, i, "bf16")
    row = state.ref.gaps(got_c, got_inertia, want_c, want_inertia, got_lab, want_lab)
    row["iterations_gap"] = 0
    return row
