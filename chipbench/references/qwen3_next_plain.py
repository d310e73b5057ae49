"""Plain reference for the ``qnext_step`` kind: Qwen3-Next (Qwen, 2025-09; HF
``modeling_qwen3_next.py``) forward, loss, gradients and AdamW in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``. It imports
nothing of heat_tpu; what it shares with the OLMoE reference (AdamW, the
batches, the numbers of ``correct``) it takes from ``olmoe_plain.py``.

    norm(x; w)  = x rsqrt(mean x^2 + eps) (1 + w)               zero-centred, w starts at 0
    x  = Embed[tokens]
    block i:  x = x + mixer_i(norm(x; g_in));  x = x + moe(norm(x; g_post))
      mixer_i = gated attention where (i + 1) % full_attention_interval == 0, else Gated DeltaNet
    Gated DeltaNet (Hk key heads, Hv value heads, r = Hv / Hk):
      [q, k, v, z] = h W_qkvz;  [b, a] = h W_ba
      [q, k, v] = silu(causal depthwise conv of 4 taps over concat(q, k, v))
      q, k -> l2-normalised over their head (eps 1e-6), q scaled by Dk^-1/2; key head j serves value heads j r .. j r + r - 1
      beta_t = sigmoid(b_t);  alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))      per value head
      S_0 = 0;  S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T;  o_t = S_t^T q_t
      out = ((o_t rsqrt(mean o_t^2 + eps)) g_o * silu(z_t)) W_out                      g_o a plain gain, starts at 1
    gated attention (H query heads on Hkv key-value heads of size D):
      [q, g] = h W_q (a head's q beside its g);  k = h W_k;  v = h W_v
      q, k = norm over each head (g_q, g_k);  rotary (rotate-half) on the first partial_rotary_factor D of a head
      causal softmax(q k^T / sqrt(D)) v, key-value head j serving query heads j H/Hkv ..;  out = (attn * sigmoid(g)) W_o
    experts: p = softmax(h W_r) over all E;  (w_j, e_j) = top-k of p, w_j / sum_j w_j
      y = sum_{j: e_j held} w_j E_{e_j}(h) + sigmoid(h w_s) E_shared(h),   E(h) = (silu(h Wg) * (h Wu)) Wd
    logits = norm(x; g_f) W_head
    loss = CE(next token) + c_lb * E * sum_e f_e P_e (+ c_z * mean(logsumexp(r)^2), 0 for this family)

Departures from HF's model: (1) the multi-token-prediction module of the
release is left out (it is not in ``config.json`` and HF's model drops it too);
(2) **the share**: this is one of ``num_experts / num_experts_held`` ranks that
divide every expert layer: the router, the top-k and its normalisation are over
all experts, but only experts ``first_expert_held .. + num_experts_held - 1``
have weights here, and what the other experts would add is left out of the
layer's result (the shared expert is whole); the vocabulary is a slice, which
is a smaller vocabulary; (3) ``W_qkvz`` is laid out ``[q | k | v | z]`` (HF
interleaves the four by key head: a permutation of columns), the convolution's
weight is ``(channels, taps)``; (4) ``f_e`` is the share of the N*k
assignments on expert ``e`` and the layers' terms are averaged, as in
``olmoe_plain.py``; (5) only so that it fits beside its optimizer state: each
block is recomputed in the backward pass, the recurrence keeps its state once
every 64 positions and recomputes between, attention is taken a head at a
time with K and V repeated, the held experts are a loop, the cross-entropy a
sequence at a time; (6) no padding, no mask but the causal one, no cache, no
dropout.

``products="bf16"`` is the **control** a precision below the configuration's:
bfloat16 operands *and* a bfloat16 accumulator in every matrix product,
bfloat16 norms, router softmax and top-k weights (``olmoe_plain._Numerics``).
``products="bf16_state"`` is the control for the delta rule alone: every
product float32, but the state ``S`` is stored in bfloat16 after every
position and ``alpha`` and ``beta`` are rounded to bfloat16. ``correct`` must
refuse both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.olmoe_plain import (  # noqa: F401  (the kind reads these from here)
    _freeze, _Numerics, _normal, _thaw, adamw_init, adamw_update, batch, rel_gap, rms_gap,
    routing_disagreement, update_gaps, zipf_cdf,
)

GROUPS = ("embed", "attention", "deltanet", "norms", "router", "experts", "shared", "head")
RECURRENCE_BLOCK = 64  # positions between two kept states (memory only)
WRITES_TO_STREAM = ("wo", "w_out", "wd", "ws_d")


# -- what a run is made from ------------------------------------------------------


def is_attention(c: dict, i: int) -> bool:
    return (i + 1) % c["full_attention_interval"] == 0


def param_shapes(c: dict) -> dict:
    d, v = c["hidden_size"], c["vocab_size"]
    e, held, f, fs = c["num_experts"], c["num_experts_held"], c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    h, hkv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    key_dim = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    value_dim = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    moe = {
        "g_post": (d,), "wr": (d, e), "wg": (held, d, f), "wu": (held, d, f), "wd": (held, f, d),
        "ws_g": (d, fs), "ws_u": (d, fs), "ws_d": (fs, d), "ws_r": (d, 1),
    }
    attention = {
        "g_in": (d,), "wq": (d, h * 2 * dh), "wk": (d, hkv * dh), "wv": (d, hkv * dh),
        "g_q": (dh,), "g_k": (dh,), "wo": (h * dh, d),
    }
    deltanet = {
        "g_in": (d,), "w_qkvz": (d, 2 * key_dim + 2 * value_dim), "w_ba": (d, 2 * c["linear_num_value_heads"]),
        "conv": (2 * key_dim + value_dim, c["linear_conv_kernel_dim"]),
        "a_log": (c["linear_num_value_heads"],), "dt_bias": (c["linear_num_value_heads"],),
        "g_o": (c["linear_value_head_dim"],), "w_out": (value_dim, d),
    }
    return {
        "embed": (v, d), "g_f": (d,), "head": (d, v),
        "layers": [
            {**(attention if is_attention(c, i) else deltanet), **moe} for i in range(c["num_hidden_layers"])
        ],
    }


def group_of(name: str) -> str:
    if name.startswith("g_"):
        return "norms"
    if name in ("embed", "head"):
        return name
    if name == "wr":
        return "router"
    if name in ("wg", "wu", "wd"):
        return "experts"
    if name.startswith("ws_"):
        return "shared"
    return "attention" if name in ("wq", "wk", "wv", "wo") else "deltanet"


def init_params(seed: int, c: dict, std: float = 0.02, out_std=None) -> dict:
    """Float32, made on the device, leaf ``i`` (in the order of
    ``param_shapes``) from ``fold_in(PRNGKey(seed mod 2^31), i)``: matrices
    normal(0, std), those that write into the residual stream (``wo``,
    ``w_out``, every down projection) normal(0, out_std); zero-centred norm
    gains 0, the gated norm's gain 1; ``a_log = log U(0, 16]``, ``dt_bias = 1``
    (HF); the convolution's taps U(-1/2, 1/2) (PyTorch's default for a
    depthwise ``Conv1d`` of 4 taps)."""
    out_std = std if out_std is None else out_std
    shapes = param_shapes(c)
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.PRNGKey(seed % (2**31))
    out = []
    for i, (path, shape) in enumerate(paths):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        if name == "g_o" or name == "dt_bias":
            out.append(jnp.ones(shape, jnp.float32))
        elif name.startswith("g_"):
            out.append(jnp.zeros(shape, jnp.float32))
        elif name == "a_log":
            out.append(jnp.log(16.0 * (1.0 - jax.random.uniform(k, shape, jnp.float32))))
        elif name == "conv":
            out.append(jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5))
        else:
            out.append(_normal(k, shape, out_std if name in WRITES_TO_STREAM else std))
    return jax.tree.unflatten(tree, out)


# -- the model --------------------------------------------------------------------


def norm(num, x, w, eps):
    """The zero-centred RMSNorm."""
    return num.rms(x, 1.0 + w, eps)


def _rotary(x, theta, rot):
    """x: (B, T, H, D): rotate-half over the first ``rot`` of D, angles
    t * theta^(-2i/rot); the rest of the head passes."""
    t = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    head, rest = x[..., :rot], x[..., rot:]
    turned = jnp.concatenate([-head[..., rot // 2:], head[..., : rot // 2]], axis=-1)
    return jnp.concatenate([head * jnp.cos(ang) + turned * jnp.sin(ang), rest], axis=-1)


def _attention(num, c, lp, h):
    b, t, d = h.shape
    heads, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, rot = c["rms_norm_eps"], int(dh * c["partial_rotary_factor"])
    qg = num.mm(h, lp["wq"]).reshape(b, t, heads, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = num.mm(h, lp["wk"]).reshape(b, t, kv, dh)
    v = num.mm(h, lp["wv"]).reshape(b, t, kv, dh)
    q = _rotary(norm(num, q, lp["g_q"], eps), c["rope_theta"], rot)
    k = _rotary(norm(num, k, lp["g_k"], eps), c["rope_theta"], rot)
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(qkv):  # (T, D) each
        qh, kh, vh = qkv
        s = jnp.where(causal, num.mm(qh, kh.T) / np.sqrt(dh), -jnp.inf)
        return num.mm(jax.nn.softmax(s.astype(num.soft), axis=-1).astype(jnp.float32), vh)

    by_head = lambda a: a.transpose(0, 2, 1, 3).reshape(b * heads, t, dh)  # noqa: E731
    o = jax.lax.map(one_head, (by_head(q), by_head(k), by_head(v)))
    o = o.reshape(b, heads, t, dh).transpose(0, 2, 1, 3) * jax.nn.sigmoid(gate)
    return num.mm(o.reshape(b, t, heads * dh), lp["wo"])


def delta_rule(q, k, v, alpha, beta, low_state=False):
    """The recurrence, a position at a time: q, k ``(B, T, H, Dk)``, v ``(B, T,
    H, Dv)``, alpha and beta ``(B, T, H)``; returns o ``(B, T, H, Dv)``. The
    state is kept once every ``RECURRENCE_BLOCK`` positions for the backward
    pass and recomputed between (positions past ``T`` neither decay nor
    write). ``low_state``: the state stored in bfloat16 after each position,
    alpha and beta rounded to bfloat16."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    # reduce_precision: a pair of casts is what a compiler that allows excess
    # precision, as the TPU's does, drops (it did: the control read 0 on the chip)
    low = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)  # noqa: E731
    if low_state:
        alpha, beta = low(alpha), low(beta)
    pad = -t % RECURRENCE_BLOCK
    widen = lambda a, fill: jnp.pad(  # noqa: E731
        a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2), constant_values=fill
    )
    q, k, v, alpha, beta = widen(q, 0), widen(k, 0), widen(v, 0), widen(alpha, 1), widen(beta, 0)
    blocks = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((b, (t + pad) // RECURRENCE_BLOCK, RECURRENCE_BLOCK) + a.shape[2:]), (1, 2), (0, 1)
    )

    def position(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        state = state * a_t[..., None, None]
        seen = jnp.einsum("bhde,bhd->bhe", state, k_t)
        state = state + jnp.einsum("bhd,bhe->bhde", k_t, (v_t - seen) * b_t[..., None])
        if low_state:
            state = low(state)
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(position, state, xs)

    _, o = jax.lax.scan(block, jnp.zeros((b, h, dk, dv), jnp.float32), tuple(blocks(a) for a in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, (0, 1), (1, 2)).reshape(b, t + pad, h, dv)[:, :t]


def _conv(x, w):
    """Causal depthwise convolution: y[t, c] = sum_j w[c, j] x[t - (K-1) + j, c]."""
    taps, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[:, j] for j in range(taps))


def _deltanet(num, c, lp, h, low_state=False):
    b, t, d = h.shape
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    qkvz = num.mm(h, lp["w_qkvz"])
    ba = num.mm(h, lp["w_ba"])
    qkv = jax.nn.silu(_conv(qkvz[..., : 2 * key_dim + value_dim], lp["conv"]))
    z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, t, hv, dv)
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(qkv[..., :key_dim].reshape(b, t, hk, dk)) * dk**-0.5
    k = unit(qkv[..., key_dim: 2 * key_dim].reshape(b, t, hk, dk))
    v = qkv[..., 2 * key_dim:].reshape(b, t, hv, dv)
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
    beta = jax.nn.sigmoid(ba[..., :hv])
    alpha = jnp.exp(-jnp.exp(lp["a_log"]) * jax.nn.softplus(ba[..., hv:] + lp["dt_bias"]))
    o = delta_rule(q, k, v, alpha, beta, low_state)
    o = num.rms(o, lp["g_o"], c["rms_norm_eps"]) * jax.nn.silu(z)
    return num.mm(o.reshape(b, t, value_dim), lp["w_out"])


def route(num, c, lp, h, forced=None):
    """Router logits (float32), probabilities over all experts, the top-k
    weights divided by their sum, and the experts, for tokens ``h (N, D)``.
    With ``forced (N, k)`` those experts are taken in place of the top-k, each
    at its own probability here (an entry below 0 leaves that choice free)."""
    r = num.mm(h, lp["wr"])
    p = jax.nn.softmax(r.astype(num.soft), axis=-1)
    w, e = jax.lax.top_k(p, c["num_experts_per_tok"])
    if forced is not None:  # a negative entry: this file's own choice (one program for both cases)
        e = jnp.where(forced < 0, e, forced)
        w = jnp.take_along_axis(p, e, axis=-1)
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return r, p.astype(jnp.float32), w.astype(jnp.float32), e


def _swiglu(num, h, wg, wu, wd):
    return num.mm(jax.nn.silu(num.mm(h, wg)) * num.mm(h, wu), wd)


def _experts(num, c, lp, h, forced=None):
    """h: (N, D). A loop over the held experts, each on all tokens with a zero
    weight where it was not chosen; the experts that are not held add nothing.
    Returns the layer's output, its auxiliary terms (over all experts), the
    counts, the chosen experts, the probabilities and the weights."""
    n, n_exp = h.shape[0], c["num_experts"]
    first, held = c.get("first_expert_held", 0), c["num_experts_held"]
    r, p, w, e = route(num, c, lp, h, forced)
    dense_w = jnp.zeros((n, n_exp), jnp.float32).at[jnp.arange(n)[:, None], e].add(w)

    @jax.checkpoint
    def one(acc, ex):
        wg, wu, wd, w_e = ex
        return acc + w_e[:, None] * _swiglu(num, h, wg, wu, wd), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (lp["wg"], lp["wu"], lp["wd"], dense_w.T[first:first + held])
    )
    gate = jax.nn.sigmoid(num.mm(h, lp["ws_r"]).astype(num.soft)).astype(jnp.float32)
    out = out + gate * _swiglu(num, h, lp["ws_g"], lp["ws_u"], lp["ws_d"])
    counts = jnp.zeros((n_exp,), jnp.int32).at[e.reshape(-1)].add(1)
    f = counts.astype(jnp.float32) / (n * c["num_experts_per_tok"])
    load_balance = n_exp * jnp.sum(f * p.mean(axis=0))
    router_z = jnp.mean(jax.nn.logsumexp(r, axis=-1) ** 2)
    return out, load_balance, router_z, counts, e, p, w


def hidden_states(params, tokens, c, products="float32", forced=None):
    """Final-norm output (B, T, D), and per layer the auxiliary terms, the
    counts, the chosen experts (N, k), the router's probabilities (N, E) and
    the top-k weights. ``forced (layers, N, k)`` fixes every layer's experts."""
    num = _Numerics("float32" if products == "bf16_state" else products)
    eps = c["rms_norm_eps"]
    x = params["embed"][tokens]
    b, t, d = x.shape

    def layer(i, lp, x, forced_i):
        h = norm(num, x, lp["g_in"], eps)
        if is_attention(c, i):
            x = x + _attention(num, c, lp, h)
        else:
            x = x + _deltanet(num, c, lp, h, low_state=products == "bf16_state")
        y, *rest = _experts(num, c, lp, norm(num, x, lp["g_post"], eps).reshape(b * t, d), forced_i)
        return x + y.reshape(b, t, d), rest

    aux = []
    for i, lp in enumerate(params["layers"]):
        x, rest = jax.checkpoint(functools.partial(layer, i))(lp, x, None if forced is None else forced[i])
        aux.append(rest)
    return norm(num, x, params["g_f"], eps), aux


def logits_of(params, tokens, c, products="float32", last: int = 0, forced=None):
    num = _Numerics("float32" if products == "bf16_state" else products)
    h, aux = hidden_states(params, tokens, c, products, forced)
    return num.mm(h[:, -last:], params["head"]), aux


def loss_parts(params, tokens, c, coef, products="float32", forced=None):
    """``(loss, parts)``: parts = ce, load_balance, router_z (means over the
    layers), expert_counts (layers x experts), chosen (layers x N x k), probs
    (layers x N x experts), weights (layers x N x k)."""
    num = _Numerics("float32" if products == "bf16_state" else products)
    h, aux = hidden_states(params, tokens, c, products, forced)
    t = tokens.shape[1]

    @jax.checkpoint
    def one_sequence(hs, ys):
        logits = num.mm(hs[:-1], params["head"])
        picked = jnp.take_along_axis(logits, ys[1:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    ce = jnp.sum(jax.lax.map(lambda a: one_sequence(*a), (h, tokens))) / (tokens.shape[0] * (t - 1))
    lb = jnp.mean(jnp.stack([a[0] for a in aux]))
    z = jnp.mean(jnp.stack([a[1] for a in aux]))
    loss = ce + coef["load_balance"] * lb + coef["router_z"] * z
    return loss, {
        "ce": ce, "load_balance": lb, "router_z": z,
        "expert_counts": jnp.stack([a[2] for a in aux]),
        "chosen": jnp.stack([a[3] for a in aux]),
        "probs": jnp.stack([a[4] for a in aux]),
        "weights": jnp.stack([a[5] for a in aux]),
    }


# -- steps and evaluations ----------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(0, 1))
def _train_step(params, state, tokens, c_items, o_items, products):
    c, o = _thaw(c_items), _thaw(o_items)
    (loss, parts), grads = jax.value_and_grad(loss_parts, has_aux=True)(
        params, tokens, c, o["coef"], products
    )
    params, state = adamw_update(params, grads, state, o)
    del parts["probs"]  # tokens x experts a layer: not what a step is read for
    return params, state, loss, parts


def train_step(params, state, tokens, c, o, products="float32"):
    """One optimizer step; ``params`` and ``state`` are consumed. One program,
    gradients and update together: beside 7.5 GB of parameters and moments the
    gradients of a program of their own do not fit the chip (they did not, in
    PR 30's call 4)."""
    with jax.default_matmul_precision("highest"):
        return _train_step(params, state, tokens, _freeze(c), _freeze(o), products)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _evaluate(params, tokens, c_items, coef_items, last, products, forced):
    c, coef = _thaw(c_items), _thaw(coef_items)
    (loss, parts), grads = jax.value_and_grad(loss_parts, has_aux=True)(
        params, tokens, c, coef, products, forced
    )
    logits, _ = logits_of(params, tokens, c, products, last, forced)
    return loss, parts, group_norms(grads), logits


def evaluate(params, tokens, c, coef, last, products="float32", forced=None):
    """Loss, its parts, the gradient's norm per parameter group and the
    logits of the last ``last`` positions, at ``params``. ``forced=None`` runs
    the same compiled program as a forced routing, every entry left free."""
    if forced is None:
        forced = np.full(
            (c["num_hidden_layers"], tokens.shape[0] * tokens.shape[1], c["num_experts_per_tok"]), -1, np.int32
        )
    with jax.default_matmul_precision("highest"):
        return _evaluate(params, tokens, _freeze(c), _freeze(coef), last, products, forced)


def group_norms(grads) -> dict:
    """L2 norm of the gradient over each parameter group of ``GROUPS``."""
    sq = dict.fromkeys(GROUPS, 0.0)
    for name in ("embed", "g_f", "head"):
        sq[group_of(name)] = sq[group_of(name)] + jnp.sum(grads[name] ** 2)
    for lp in grads["layers"]:
        for name, g in lp.items():
            sq[group_of(name)] = sq[group_of(name)] + jnp.sum(g**2)
    return {k: jnp.sqrt(v) for k, v in sq.items()}
