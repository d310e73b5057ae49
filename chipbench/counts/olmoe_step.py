"""Operations that one training step of the ``lm_step`` configuration needs,
from its shapes: the model's FLOPs, as an MFU counts them.

A token's forward pass: the chosen experts' three products (top-k x 3 x 2 x
hidden x expert width), the four attention projections, the causal scores
and values (each query against the keys up to its own position: counted
once, not as the full square), the router and the output head; the backward
pass twice that. Nothing recomputed is counted (the program computes the
head's logits again in its backward pass), nor the optimizer, the norms or
the softmaxes. ``bytes`` is 0: the share this feeds is a share of the peak
FLOP/s alone.
"""


def forward_flops_per_token(config: dict) -> dict:
    d, f = config["hidden_size"], config["intermediate_size"]
    t, layers = config["sequence_length"], config["num_hidden_layers"]
    return {
        "experts": layers * config["num_experts_per_tok"] * 3 * 2 * d * f,
        "projections": layers * 4 * 2 * d * d,
        "attention": layers * 2 * 2 * d * (t + 1) // 2,
        "router": layers * 2 * d * config["num_experts"],
        "head": 2 * d * config["vocab_size"],
    }


def work(config: dict, chips: int) -> dict:
    tokens = config["sequences_per_step"] * config["sequence_length"]
    return {"flops": 3 * tokens * sum(forward_flops_per_token(config).values()), "bytes": 0}
