"""Operations that one training step of the ``lfm2_step`` configuration needs,
from its shapes: the model's FLOPs, as an MFU counts them.

A token's forward pass. A short-convolution block: the two projections (``W_in``
to three times the hidden size, ``W_out``); the taps and the gates are
elementwise and not counted. An attention block: the projections (queries, keys
and values of the key-value heads, the output) at **heads of 64**, what the model
has and not the 128 lanes the kernels pad them to, and the causal scores and
values, each query against the keys up to its own position (counted once, not
as the full square: ``swa_attention.pairs_per_head`` at a window of the
sequence). A dense block: the SwiGLU of ``intermediate_size``. An expert block:
the router over all experts and the held experts' rows, ``held_load`` x an even
routing (top-k x held / experts assignments a token land here at 1.0; the
reader gives the share the run's counters saw). The head over the vocabulary's
slice, once: it is the embedding table. The backward pass twice that. Nothing
recomputed is counted (every block runs again in the backward pass), nor the
optimizer, the norms, the gates, the taps or the softmaxes. ``bytes`` is 0: the
share this feeds is a share of the peak FLOP/s alone.
"""

from chipbench.counts.swa_attention import pairs_per_head


def layer_kinds(config: dict):
    """``(short-convolution blocks, attention blocks)`` of the layers held."""
    first = config.get("first_block", 0)
    kinds = config["layer_types"][first:first + config["num_hidden_layers"]]
    return sum(k == "conv" for k in kinds), sum(k != "conv" for k in kinds)


def forward_flops_per_token(config: dict, held_load: float = 1.0) -> dict:
    d, t, layers = config["hidden_size"], config["sequence_length"], config["num_hidden_layers"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = d // heads
    conv, attention = layer_kinds(config)
    dense = config["num_dense_layers"]
    share = held_load * config["num_experts_per_tok"] * config["num_experts_held"] / config["num_experts"]
    return {
        "conv_projections": conv * 2 * d * (3 * d + d),
        "attention_projections": attention * 2 * d * (2 * heads * dh + 2 * kv * dh),
        "attention": attention * 2 * 2 * heads * dh * pairs_per_head(t, t) // t,
        "dense": dense * 3 * 2 * d * config["intermediate_size"],
        "router": (layers - dense) * 2 * d * config["num_experts"],
        "experts": int((layers - dense) * share * 3 * 2 * d * config["moe_intermediate_size"]),
        "head": 2 * d * config["vocab_size"],
    }


def work(config: dict, chips: int, held_load: float = 1.0) -> dict:
    tokens = config["sequences_per_step"] * config["sequence_length"]
    return {"flops": 3 * tokens * sum(forward_flops_per_token(config, held_load).values()), "bytes": 0}


def attention_work(config: dict, chips: int) -> dict:
    """The full causal flash kernels' own work, forward and backward, at heads
    of 64: the pairs' products, and the bytes a kernel has to move once
    (``swa_attention.work``'s count at a window of the sequence: q, k, v, the
    output and its cotangent in bfloat16, a log-sum-exp a row, dq, dk, dv)."""
    b, t = config["sequences_per_step"], config["sequence_length"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["hidden_size"] // h
    _, layers = layer_kinds(config)
    forward_flops = b * h * pairs_per_head(t, t) * 2 * 2 * dh
    rows = b * t * dh * 2  # one head's q, k, v, o or a cotangent, bfloat16
    lse = b * t * h * 4
    forward = rows * (2 * h + 2 * kv) + lse
    backward = rows * (3 * h + 2 * kv) + lse + rows * (h + 2 * kv)
    return {"flops": 3 * layers * forward_flops, "bytes": layers * (forward + backward)}


def conv_mixer_work(config: dict, chips: int) -> dict:
    """What the short-convolution mixers' modules must do in a step, forward
    and backward: the two projections' products (``conv_projections`` above,
    three times: forward, and the two products of the backward pass) and, once
    each way, the bytes of what enters and leaves a mixer: forward its input
    and its output (tokens x hidden, float32: the stream's), backward those two
    cotangents and the input again; the weights read forward and backward and
    their gradient written (float32). The gates, the taps and ``B``, ``C``,
    ``x`` are counted nowhere: fused into the products they cost no pass (TPU
    v5e, PR 39: XLA does fuse them), and what runs a second time under
    rematerialisation is not work either: the share this feeds is low by that."""
    d, tokens = config["hidden_size"], config["sequences_per_step"] * config["sequence_length"]
    mixers, _ = layer_kinds(config)
    weights = d * 3 * d + d * d + d * config["conv_L_cache"]
    return {
        "flops": 3 * tokens * forward_flops_per_token(config)["conv_projections"],
        "bytes": mixers * 4 * (5 * tokens * d + 3 * weights),
    }
