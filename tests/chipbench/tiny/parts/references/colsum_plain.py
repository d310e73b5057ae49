import numpy as np


def make(seed, rows, features):
    return np.random.default_rng(seed).standard_normal((rows, features)).astype(np.float32)


def column_sums(x):
    return x.astype(np.float64).sum(axis=0)


def gap(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)) / np.max(np.abs(want)))
