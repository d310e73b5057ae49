"""Bytes that one AdamW update of the ``lm_step`` configuration moves, from
its shapes: for every float32 parameter, read the parameter, its gradient and
both moments, write the parameter and both moments: 28 bytes. The FLOPs (a
dozen a parameter) never bind.
"""


def parameters(config: dict) -> int:
    d, f, e = config["hidden_size"], config["intermediate_size"], config["num_experts"]
    layer = 3 * e * d * f + 4 * d * d + d * e + 4 * d
    return config["num_hidden_layers"] * layer + 2 * config["vocab_size"] * d + d


def work(config: dict, chips: int) -> dict:
    n = parameters(config)
    return {"bytes": 28 * n, "flops": 12 * n}
