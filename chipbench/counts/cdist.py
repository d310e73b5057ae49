"""Bytes and operations that a distance matrix of X against itself needs,
from its shapes: the m x m float32 result written once, X read once as rows
and once as columns, and one multiply and one add for each of m x m x d
products.
"""


def work(config: dict, chips: int) -> dict:
    m, d = config["rows"], config["features"]
    return {
        "bytes": m * m * 4 + 2 * m * d * 4,
        "flops": 2 * m * m * d,
    }
