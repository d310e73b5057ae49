"""Operations that one training step of the ``ouro_step`` configuration needs,
from its shapes: the model's FLOPs, as an MFU counts them.

A token's forward pass: every one of the ``total_ut_steps`` passes through the
``num_hidden_layers`` blocks (the four attention projections at
``num_attention_heads`` and ``num_key_value_heads`` heads of ``head_dim``, the
causal scores and values, each query against the keys up to its own position:
counted once, not as the full square, ``swa_attention.pairs_per_head`` at a
window of the sequence; the SwiGLU's three products) and every exit's pass
through the head (``total_ut_steps`` of them: the loss reads all). The backward
pass twice that. Nothing recomputed is counted (every block application runs
again in the backward pass), nor the optimizer, the norms, the gate's one
product a position or the softmaxes. ``bytes`` is 0: the share this feeds is a
share of the peak FLOP/s alone.
"""

from chipbench.counts.swa_attention import pairs_per_head


def forward_flops_per_token(config: dict) -> dict:
    d, t, f = config["hidden_size"], config["sequence_length"], config["intermediate_size"]
    heads, kv, dh = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    applications = config["total_ut_steps"] * config["num_hidden_layers"]
    return {
        "projections": applications * 2 * d * (2 * heads * dh + 2 * kv * dh),
        "attention": applications * 2 * 2 * heads * dh * pairs_per_head(t, t) // t,
        "feed_forward": applications * 3 * 2 * d * f,
        "head": config["total_ut_steps"] * 2 * d * config["vocab_size"],
    }


def work(config: dict, chips: int) -> dict:
    tokens = config["sequences_per_step"] * config["sequence_length"]
    return {"flops": 3 * tokens * sum(forward_flops_per_token(config).values()), "bytes": 0}


def attention_work(config: dict, chips: int) -> dict:
    """The full causal flash kernels' own work over the ``total_ut_steps x
    num_hidden_layers`` applications, forward and backward: the pairs'
    products, and the bytes a kernel has to move once (``lfm2_step``'s count:
    q, k, v, the output and its cotangent in bfloat16, a log-sum-exp a row, dq,
    dk, dv)."""
    b, t = config["sequences_per_step"], config["sequence_length"]
    h, kv, dh = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    applications = config["total_ut_steps"] * config["num_hidden_layers"]
    forward_flops = b * h * pairs_per_head(t, t) * 2 * 2 * dh
    rows = b * t * dh * 2  # one head's q, k, v, o or a cotangent, bfloat16
    lse = b * t * h * 4
    forward = rows * (2 * h + 2 * kv) + lse
    backward = rows * (3 * h + 2 * kv) + lse + rows * (h + 2 * kv)
    return {"flops": 3 * applications * forward_flops, "bytes": applications * (forward + backward)}


def parameters(config: dict) -> int:
    """The parameters the chip holds: what ``cut_arithmetic`` adds up."""
    d, f, v = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    heads, kv, dh = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    block = d * (2 * heads * dh + 2 * kv * dh) + 3 * d * f + 4 * d
    return config["num_hidden_layers"] * block + 2 * v * d + d + d + 1
