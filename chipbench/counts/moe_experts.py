"""Operations and bytes of the expert layers' grouped matmuls in one training
step, from the shapes: for each of the three products (gate, up, down) the
forward product and the two of its backward pass, over the tokens x top-k
sorted rows. Bytes: each product reads its two operands in bfloat16 and
writes a float32 result once.
"""


def work(config: dict, chips: int) -> dict:
    rows = config["sequences_per_step"] * config["sequence_length"] * config["num_experts_per_tok"]
    d, f, e = config["hidden_size"], config["intermediate_size"], config["num_experts"]
    layers = config["num_hidden_layers"]
    wide, narrow, weights = rows * d, rows * f, e * d * f
    # forward: y = x W; backward: dx = dy W^T, dW = x^T dy; three such products
    one = (2 * (wide + weights) + 4 * narrow) + (2 * (narrow + weights) + 4 * wide) + (2 * (wide + narrow) + 4 * weights)
    return {"flops": layers * 3 * 3 * 2 * rows * d * f, "bytes": layers * 3 * one}
