"""A kind that only the tests' tiny manifest names: column sums of a
DNDarray."""

import numpy as np


class State:
    pass


def setup(config, comm, seed, reference):
    import heat_tpu as ht

    s = State()
    s.config, s.ref = config, reference
    s.host = reference.make(seed, config["rows"], config["features"])
    s.x = ht.array(s.host, split=0, comm=comm)
    return s


def items_per_call(config, chips):
    return config["rows"]


def call(state, i):
    return state.x.sum(axis=0)


def outputs(result):
    return result.larray


def summary(result):
    return np.asarray(result.larray)


def check(state, calls, last):
    want = state.ref.column_sums(state.host)
    return [(c.index, {"sum_gap": state.ref.gap(c.summary, want)}) for c in calls]
