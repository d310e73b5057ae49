"""Operations that one training step of the ``qnext_step`` configuration needs,
from its shapes: the model's FLOPs, as an MFU counts them.

A token's forward pass. A Gated DeltaNet block: the projections (W_qkvz, W_ba,
W_out) and the delta rule in chunks (``gdn_scan.rule_flops_per_token``). A
gated-attention block: the projections (queries with their gates, keys and
values of the key-value heads, the output) and the causal scores and values
(each query against the keys up to its own position: counted once, not as the
full square). Every block: the router over all experts, the shared expert and
its gate, and the held experts at an even routing (top-k x held / experts
assignments a token land here). The head over the vocabulary's slice. The
backward pass twice that. Nothing recomputed is counted (every block runs
again in the backward pass, the mixers twice), nor the triangular solve's
products, the optimizer, the norms, the convolution or the softmaxes.
``bytes`` is 0: the share this feeds is a share of the peak FLOP/s alone.
"""


def rule_flops_per_token(config: dict) -> int:
    """The delta rule in chunks of ``delta_chunk``, a token and value head: five
    products of chunk x head size a row (keys on keys, queries on keys, the
    solve on values and on keys, scores on values) and three of head size x
    head size (the state read by the keys, by the queries, and written)."""
    c, dk, dv = config["delta_chunk"], config["linear_key_head_dim"], config["linear_value_head_dim"]
    a_head = 2 * c * (3 * dk + 2 * dv) + 3 * 2 * dk * dv
    return config["linear_num_value_heads"] * a_head


def forward_flops_per_token(config: dict) -> dict:
    d, t, layers = config["hidden_size"], config["sequence_length"], config["num_hidden_layers"]
    period = config["full_attention_interval"]
    full = sum((i + 1) % period == 0 for i in range(layers))
    linear = layers - full
    key_dim = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value_dim = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    heads, kv, dh = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    f, fs = config["moe_intermediate_size"], config["shared_expert_intermediate_size"]
    share = config["num_experts_per_tok"] * config["num_experts_held"] / config["num_experts"]
    return {
        "gdn_projections": linear * 2 * d * (2 * key_dim + 2 * value_dim + 2 * config["linear_num_value_heads"] + value_dim),
        "gdn_rule": linear * rule_flops_per_token(config),
        "attention_projections": full * 2 * d * (2 * heads * dh + 2 * kv * dh + heads * dh),
        "attention": full * 2 * 2 * heads * dh * (t + 1) // 2,
        "router": layers * 2 * d * config["num_experts"],
        "shared": layers * (3 * 2 * d * fs + 2 * d),
        "experts": int(layers * share * 3 * 2 * d * f),
        "head": 2 * d * config["vocab_size"],
    }


def work(config: dict, chips: int) -> dict:
    tokens = config["sequences_per_step"] * config["sequence_length"]
    return {"flops": 3 * tokens * sum(forward_flops_per_token(config).values()), "bytes": 0}
