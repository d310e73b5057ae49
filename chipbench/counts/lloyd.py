"""Bytes and operations that a K-Means fit needs, from its shapes: what the
algorithm needs, not what today's program does.

``max_iter`` update passes and one final assignment pass each read X once;
the labels are written once, in the type the API delivers (int64). A pass
takes, for each row, ``k`` centres times ``d`` features, a multiply and an
add for the distances and again for the cluster sums: 4 rows k d, at the
published ``k`` (not a kernel's lane-padded one).
"""


def work(config: dict, chips: int) -> dict:
    rows = config["rows"]
    k, d = config["n_clusters"], config["features"]
    passes = config["max_iter"] + 1
    return {
        "bytes": passes * rows * d * 4 + rows * 8,
        "flops": passes * 4 * rows * k * d,
    }
