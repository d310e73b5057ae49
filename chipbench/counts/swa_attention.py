"""Operations and bytes of the sliding-window attention kernels in one training
step, forward and backward, from the shapes. A position sees itself and the
``sliding_window - 1`` before it, so a head of ``T`` positions has
``sum_t min(t + 1, window)`` score pairs: the **band**, counted as a band.
Forward a pair costs two products of the head size (scores, values), the
backward pass twice that (as a model's FLOPs are counted: the scores the
backward kernels compute again are not counted, nor the forward pass that
rematerialisation runs again). Bytes are what a banded kernel has to move once:
forward it reads q, k, v and writes the output (bfloat16) and a log-sum-exp a
row (float32); backward it reads those and the output's cotangent and writes
dq, dk, dv (k, v, dk, dv on the key-value heads).
"""


def pairs_per_head(t: int, window: int) -> int:
    w = min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def sliding_layers(config: dict) -> int:
    period = config["global_attn_every_n_layers"]
    return sum((i + 1) % period != 0 for i in range(config["num_hidden_layers"]))


def work(config: dict, chips: int) -> dict:
    b, t = config["sequences_per_step"], config["sequence_length"]
    h, kv, dh = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    forward_flops = b * h * pairs_per_head(t, config["sliding_window"]) * 2 * 2 * dh
    rows = b * t * dh * 2  # one head's q, k, v, o or a cotangent, bfloat16
    lse = b * t * h * 4
    forward = rows * (2 * h + 2 * kv) + lse
    backward = rows * (3 * h + 2 * kv) + lse + rows * (h + 2 * kv)
    layers = sliding_layers(config)
    return {"flops": 3 * layers * forward_flops, "bytes": layers * (forward + backward)}
