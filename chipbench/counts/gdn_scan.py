"""Operations and bytes of the delta rule in one training step, forward and
backward, from the shapes: the chunk products and the state updates
(``qnext_step.rule_flops_per_token``; the backward pass twice the forward), and
what the rule has to move once each: forward it reads q, k, v (bfloat16
operands), the decay and beta (float32 a head and position), writes the output
(float32) and the state once a chunk (float32); backward it reads those and the
output's cotangent again and writes the five cotangents.
"""

from chipbench.counts.qnext_step import rule_flops_per_token


def work(config: dict, chips: int) -> dict:
    tokens = config["sequences_per_step"] * config["sequence_length"]
    period, layers = config["full_attention_interval"], config["num_hidden_layers"]
    linear = sum((i + 1) % period != 0 for i in range(layers))
    h, dk, dv = config["linear_num_value_heads"], config["linear_key_head_dim"], config["linear_value_head_dim"]
    chunks = config["sequences_per_step"] * -(-config["sequence_length"] // config["delta_chunk"])
    operands = tokens * h * (2 * dk + dv) * 2  # q, k, v in bfloat16
    gates = tokens * h * 2 * 4
    output = tokens * h * dv * 4
    states = chunks * h * dk * dv * 4
    forward = operands + gates + output + states
    backward = operands + gates + output + states + tokens * h * (2 * dk + dv) * 4 + gates
    return {
        "flops": 3 * linear * tokens * rule_flops_per_token(config),
        "bytes": linear * (forward + backward),
    }
