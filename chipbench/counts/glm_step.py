"""Operations that one training step of the ``glm_step`` configuration needs,
from its shapes: the model's FLOPs, as an MFU counts them.

A token's forward pass. Every block, the prediction module's among them (it is
one more whole block): the latent attention's five projections (the queries
down to their latent and up to ``heads x (nope + rope)``, the keys and values
down to their latent beside the one rotary key and up to ``heads x (nope +
v)``, the output) and the causal scores and values at **heads of ``nope + rope``
for queries and keys and ``v`` for values**, each query against the keys up to
its own position (counted once, not as the full square:
``swa_attention.pairs_per_head`` at a window of the sequence). A dense block: the
SwiGLU of ``intermediate_size``. An expert block: the router over all experts,
the shared expert, and the held experts' rows, ``held_load`` x an even routing
(top-k x held / experts assignments a token land here at 1.0; the reader gives
the share the run's counters saw). The module's merge (``W_eh``, twice the
hidden size in). The head over the vocabulary's slice, **twice**: the trunk's
pass and the module's. The backward pass twice that. Nothing recomputed is
counted (every block runs again in the backward pass), nor the optimizer, the
norms, rotary, the joins or the softmaxes; the module is counted over all ``T``
positions, as the program runs it (``T - 1`` carry a prediction). ``bytes`` is
0: the share this feeds is a share of the peak FLOP/s alone.
"""

from chipbench.counts.swa_attention import pairs_per_head


def blocks(config: dict) -> int:
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def forward_flops_per_token(config: dict, held_load: float = 1.0) -> dict:
    d, t, every = config["hidden_size"], config["sequence_length"], blocks(config)
    heads, qr, kvr = config["num_attention_heads"], config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, vd = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    f, modules = config["moe_intermediate_size"], config["num_nextn_predict_layers"]
    dense = config["first_k_dense_replace"]
    share = held_load * config["num_experts_per_tok"] * config["num_experts_held"] / config["n_routed_experts"]
    return {
        "latent_projections": every * 2 * (
            d * qr + qr * heads * (nope + rope) + d * (kvr + rope) + kvr * heads * (nope + vd) + heads * vd * d
        ),
        "attention": every * 2 * heads * (nope + rope + vd) * pairs_per_head(t, t) // t,
        "dense": dense * 3 * 2 * d * config["intermediate_size"],
        "router": (every - dense) * 2 * d * config["n_routed_experts"],
        "shared": (every - dense) * config["n_shared_experts"] * 3 * 2 * d * f,
        "experts": int((every - dense) * share * 3 * 2 * d * f),
        "merge": modules * 2 * 2 * d * d,
        "head": (1 + modules) * 2 * d * config["vocab_size"],
    }


def work(config: dict, chips: int, held_load: float = 1.0) -> dict:
    tokens = config["sequences_per_step"] * config["sequence_length"]
    return {"flops": 3 * tokens * sum(forward_flops_per_token(config, held_load).values()), "bytes": 0}


def attention_work(config: dict, chips: int) -> dict:
    """The full causal flash kernels' own work, forward and backward, over
    every block: the pairs' products at a head of ``nope + rope`` for queries
    and keys and ``v`` for values, and the bytes a kernel has to move once
    (``swa_attention.work``'s count at a window of the sequence with as many
    key-value heads as query heads: q, k, v, the output and its cotangent in
    bfloat16, a log-sum-exp a row, dq, dk, dv)."""
    b, t = config["sequences_per_step"], config["sequence_length"]
    h, qk, vd = config["num_attention_heads"], config["qk_nope_head_dim"] + config["qk_rope_head_dim"], config["v_head_dim"]
    forward_flops = b * h * pairs_per_head(t, t) * 2 * (qk + vd)
    row = lambda width: b * t * h * width * 2  # noqa: E731  (every head's q, k, v, o or a cotangent, bfloat16)
    lse = b * t * h * 4
    forward = 2 * row(qk) + 2 * row(vd) + lse  # q, k; v, o
    backward = forward + row(vd) + 2 * row(qk) + row(vd)  # those and do; dq, dk, dv
    return {"flops": 3 * blocks(config) * forward_flops, "bytes": blocks(config) * (forward + backward)}
