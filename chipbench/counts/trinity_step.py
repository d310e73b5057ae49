"""Operations that one training step of the ``trinity_step`` configuration
needs, from its shapes: the model's FLOPs, as an MFU counts them.

A token's forward pass. Every block: the attention projections (queries with
their gates, keys and values of the key-value heads, the output) and the
scores and values, in a full layer each query against the keys up to its own
position (counted once, not as the full square), in a sliding layer against
its window: **the band counted as a band** (``swa_attention.pairs_per_head``).
A dense block: the SwiGLU of ``intermediate_size``. An expert block: the router
over all experts, the shared expert, and the held experts at an even routing
(top-k x held / experts assignments a token land here). The head over the
vocabulary's slice. The backward pass twice that. Nothing recomputed is counted
(every block runs again in the backward pass), nor the optimizer, the norms,
the gates or the softmaxes. ``bytes`` is 0: the share this feeds is a share of
the peak FLOP/s alone.
"""

from chipbench.counts.swa_attention import pairs_per_head, sliding_layers


def forward_flops_per_token(config: dict) -> dict:
    d, t, layers = config["hidden_size"], config["sequence_length"], config["num_hidden_layers"]
    heads, kv, dh = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    sliding = sliding_layers(config)
    dense = config["num_dense_layers"]
    f = config["moe_intermediate_size"]
    share = config["num_experts_per_tok"] * config["num_experts_held"] / config["num_experts"]
    return {
        "attention_projections": layers * 2 * d * (2 * heads * dh + 2 * kv * dh + heads * dh),
        "attention_full": (layers - sliding) * 2 * 2 * heads * dh * (t + 1) // 2,
        "attention_sliding": sliding * 2 * 2 * heads * dh * pairs_per_head(t, config["sliding_window"]) // t,
        "dense": dense * 3 * 2 * d * config["intermediate_size"],
        "router": (layers - dense) * 2 * d * config["num_experts"],
        "shared": (layers - dense) * config["num_shared_experts"] * 3 * 2 * d * f,
        "experts": int((layers - dense) * share * 3 * 2 * d * f),
        "head": 2 * d * config["vocab_size"],
    }


def work(config: dict, chips: int) -> dict:
    tokens = config["sequences_per_step"] * config["sequence_length"]
    return {"flops": 3 * tokens * sum(forward_flops_per_token(config).values()), "bytes": 0}
