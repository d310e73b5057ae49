"""Plain reference for the ``trinity_step`` kind: Trinity-Mini (Arcee, 2025-12;
``config.json`` of ``arcee-ai/Trinity-Mini``, ``model_type`` ``afmoe``; HF
``modeling_afmoe.py``) forward, loss, gradients, AdamW and the balance rule of
its routers' biases in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. It imports nothing of heat_tpu;
what it shares with the other references (AdamW's arithmetic, the batches, the
numbers of ``correct``) it takes from ``olmoe_plain.py``.

    norm(x; w)  = x rsqrt(mean x^2 + eps) w                       plain RMSNorm, w starts at 1
    x  = Embed[tokens] * sqrt(hidden)                             (mup_enabled)
    block i:  x = x + norm(attn_i(norm(x; g_a)); g_b);  x = x + norm(ffn_i(norm(x; g_c)); g_d)
    attention (H query heads on Hkv key-value heads of size D):
      [q, g] = h W_q (a head's q beside its g);  k = h W_k;  v = h W_v;  q, k = norm over each head (g_q, g_k)
      sliding unless (i + 1) % global_attn_every_n_layers == 0:  rotary (rotate-half over all of D, theta) on q and k,
        position t sees  t - sliding_window < j <= t   (itself and the sliding_window - 1 before it)
      full:  no positions at all, t sees every j <= t
      softmax(q k^T / sqrt(D)) v under that mask, key-value head j serving query heads j H/Hkv ..;  out = (attn * sigmoid(g)) W_o
    ffn_i, i < num_dense_layers:  (silu(h Wf_g) * (h Wf_u)) Wf_d
    ffn_i, otherwise:  s = sigmoid(h W_r) over all E;  e_1..e_k = top-k of (s + b), b the layer's bias, no gradient
      w_j = s[e_j] / (sum_j s[e_j] + 1e-20) * route_scale
      y = sum_{j: e_j held} w_j E_{e_j}(h) + E_shared(h),   E(h) = (silu(h Wg) * (h Wu)) Wd        (the shared expert has no gate)
    logits = norm(x; g_f) W_head;   loss = CE(next token)  (+ c_lb * load balance + c_z * router z, both coefficients 0)
    after a step, in every expert layer:  b_e += bias_rate * sign(mean_e'(c_e') - c_e),  c the step's counts over all E

Departures from HF's model: (1) **the share**: this is one of ``num_experts /
num_experts_held`` ranks that divide every expert layer: the router, the bias,
the top-k and its normalisation are over all experts, but only experts
``first_expert_held .. + num_experts_held - 1`` have weights here, and what the
others would add is left out of the layer's result (the shared expert is
whole); the vocabulary is a slice, which is a smaller vocabulary; (2) ``W_q``
holds a head's gate beside its query (HF has ``q_proj`` and ``gate_proj``: a
permutation of columns); (3) the two auxiliary terms stay defined so that the
numbers of ``correct`` keep their meaning: ``P_e`` is the mean of ``s``
normalised to sum 1 a token, ``f_e`` the share of the N*k assignments on
expert ``e``, the layers' terms averaged; their coefficients are 0; (4) the
bias rule is torchtitan's (the code the family was trained with;
``config.json`` gives ``load_balance_coeff`` only), applied after AdamW from
the step's own counts; (5) only so that it fits beside its optimizer state:
each block is recomputed in the backward pass, one block at a time (a barrier
holds a block's second forward pass back until its cotangent has arrived),
what goes a position at a time (norms, projections, feed-forward, experts) is
taken ``TOKEN_BLOCK`` positions at a time, attention a head and ``QUERY_BLOCK``
queries at a time against all keys (a full score matrix under the mask, in
blocks of queries) with K and V repeated, the held experts are a loop, the
cross-entropy ``TOKEN_BLOCK`` positions at a time; (6) no padding, no cache, no
dropout, no document boundaries.

``products="bf16"`` is the **control** a precision below the configuration's:
bfloat16 operands *and* a bfloat16 accumulator in every matrix product,
bfloat16 norms, router sigmoid and top-k weights (``olmoe_plain._Numerics``).
The controls of the mask and the positions are keys of ``c`` that the
configuration does not have: ``full_window`` (a window on the full layers) and
``full_rotary`` (rotary on them); a wrong ``sliding_window`` is that key
changed. ``correct`` must refuse each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references import olmoe_plain
from chipbench.references.olmoe_plain import (  # noqa: F401  (the kind reads these from here)
    _freeze, _normal, _thaw, batch, rel_gap, rms_gap, routing_disagreement, zipf_cdf,
)

GROUPS = ("embed", "attention", "norms", "dense", "router", "experts", "shared", "head")
QUERY_BLOCK = 2048  # queries a score block (memory only)
TOKEN_BLOCK = 2048  # positions a block of the work that goes a position at a time (memory only)
LAST_LOGITS = 256  # positions at a sequence's end whose logits the one program of this file gives beside the gradients
WRITES_TO_STREAM = ("wo", "wd", "ws_d", "wf_d")
CLOSES_A_BRANCH = ("g_b", "g_d")  # the post-attention and post-feed-forward norms' gains


class _Numerics(olmoe_plain._Numerics):
    """``olmoe_plain._Numerics`` with the bfloat16 accumulator's loop rolled: the
    same blocks of the sum (128 terms; 16 under 256), the same rounding of the
    running sum to bfloat16 after each, as a scan. Written out, a product over
    16,384 keys is 128 products in the program's text, and the control at the
    cell's size did not compile within the 40 GiB of the chip's host (my chip
    run, PR 32, call 5)."""

    def mm(self, a, b):
        k = a.shape[-1]
        step = 128 if k >= 256 else 16
        if not self.low or b.ndim != 2 or k % step:
            return super().mm(a, b)
        blocks = k // step
        a = jnp.moveaxis(a.astype(jnp.bfloat16).reshape(a.shape[:-1] + (blocks, step)), -2, 0)
        b = b.astype(jnp.bfloat16).reshape(blocks, step, b.shape[-1])

        def add(acc, ab):
            part = jnp.matmul(ab[0], ab[1], preferred_element_type=jnp.float32)
            return (acc.astype(jnp.float32) + part).astype(jnp.bfloat16), None

        acc, _ = jax.lax.scan(add, jnp.zeros(a.shape[1:-1] + b.shape[-1:], jnp.bfloat16), (a, b))
        return acc.astype(jnp.float32)


# -- what a run is made from ------------------------------------------------------


def is_sliding(c: dict, i: int) -> bool:
    return (i + 1) % c["global_attn_every_n_layers"] != 0


def is_dense(c: dict, i: int) -> bool:
    return i < c["num_dense_layers"]


def expert_layers(c: dict):
    return [i for i in range(c["num_hidden_layers"]) if not is_dense(c, i)]


def param_shapes(c: dict) -> dict:
    d, v = c["hidden_size"], c["vocab_size"]
    e, held, f, wide = c["num_experts"], c["num_experts_held"], c["moe_intermediate_size"], c["intermediate_size"]
    h, hkv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    attention = {
        "g_a": (d,), "g_b": (d,), "g_c": (d,), "g_d": (d,),
        "wq": (d, h * 2 * dh), "wk": (d, hkv * dh), "wv": (d, hkv * dh), "g_q": (dh,), "g_k": (dh,), "wo": (h * dh, d),
    }
    dense = {"wf_g": (d, wide), "wf_u": (d, wide), "wf_d": (wide, d)}
    moe = {
        "wr": (d, e), "wg": (held, d, f), "wu": (held, d, f), "wd": (held, f, d),
        "ws_g": (d, f), "ws_u": (d, f), "ws_d": (f, d),
    }
    return {
        "embed": (v, d), "g_f": (d,), "head": (d, v),
        "layers": [{**attention, **(dense if is_dense(c, i) else moe)} for i in range(c["num_hidden_layers"])],
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
    return "dense" if name.startswith("wf_") else "attention"


def init_params(seed: int, c: dict, std: float = 0.02, out_std=None, post_gain: float = 1.0) -> dict:
    """Float32, made on the device, leaf ``i`` (in the order of
    ``param_shapes``) from ``fold_in(PRNGKey(seed mod 2^31), i)``: matrices
    normal(0, std), those that write into the residual stream (``wo`` and every
    down projection) normal(0, out_std), norm gains 1 but for the two norms
    that close a branch (``g_b``, ``g_d``: what a sandwich block really writes
    into the stream, whatever the size of the matrix before them), which are
    ``post_gain``; ``bias`` (expert layers x experts) 0: it is no parameter, and
    rides in the tree beside them."""
    out_std = std if out_std is None else out_std
    shapes = param_shapes(c)
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.PRNGKey(seed % (2**31))
    out = []
    for i, (path, shape) in enumerate(paths):
        name = path[-1].key
        if name.startswith("g_"):
            out.append(jnp.full(shape, post_gain if name in CLOSES_A_BRANCH else 1.0, jnp.float32))
        else:
            out.append(_normal(jax.random.fold_in(key, i), shape, out_std if name in WRITES_TO_STREAM else std))
    params = jax.tree.unflatten(tree, out)
    params["bias"] = jnp.zeros((len(expert_layers(c)), c["num_experts"]), jnp.float32)
    return params


# -- the model --------------------------------------------------------------------


def rotary(x, theta, first=0, turn=1.0):
    """x: (B, T, H, D), positions ``first .. first + T - 1``: rotate-half over
    all of D, angles t * theta^(-2i/D) * ``turn`` (1: rotary; 0: every angle 0,
    x as it is, which is how one compiled block serves the layers that rotate and
    those that do not)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = (first + jnp.arange(t)).astype(jnp.float32)[:, None] * inv[None, :] * turn
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def visible(t_q: int, t_k: int, first_q: int, window):
    """The mask of queries ``first_q .. first_q + t_q - 1`` on keys ``0 .. t_k -
    1``: ``j <= t`` and, with a window, ``j > t - window``."""
    q_pos = first_q + jnp.arange(t_q)[:, None]
    k_pos = jnp.arange(t_k)[None, :]
    mask = k_pos <= q_pos
    return mask if window is None else mask & (k_pos > q_pos - window)


def masked_attention(num, q, k, v, window):
    """``softmax(q k^T / sqrt(D))`` under the mask, times v: q ``(B, T, H,
    D)``, k and v ``(B, T, Hkv, D)`` (repeated over their groups), a head and
    ``QUERY_BLOCK`` queries at a time against all keys."""
    b, t, heads, dh = q.shape
    k, v = (jnp.repeat(a, heads // a.shape[2], axis=2) for a in (k, v))
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def one_head(qkv):  # (T, D) each
        qh, kh, vh = qkv

        @jax.checkpoint
        def one_block(args):
            first, qb = args
            s = jnp.where(visible(block, t, first, window), num.mm(qb, kh.T) / np.sqrt(dh), -jnp.inf)
            return num.mm(jax.nn.softmax(s.astype(num.soft), axis=-1).astype(jnp.float32), vh)

        firsts = jnp.arange(0, t, block)
        return jax.lax.map(one_block, (firsts, qh.reshape(t // block, block, dh))).reshape(t, dh)

    by_head = lambda a: a.transpose(0, 2, 1, 3).reshape(b * heads, t, dh)  # noqa: E731
    o = jax.lax.map(one_head, (by_head(q), by_head(k), by_head(v)))
    return o.reshape(b, heads, t, dh).transpose(0, 2, 1, 3)


def _project(num, c, lp, h, first, turn):
    """q, its gate, k and v of the positions ``first ..`` from ``h (B, T', D)``,
    q and k behind their head norms and rotary at ``turn`` x its angles."""
    b, t, d = h.shape
    heads, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    # a head's query and its gate lie side by side in W_q: two products of the two halves
    wq = lp["wq"].reshape(d, heads, 2 * dh)
    q = num.mm(h, wq[..., :dh].reshape(d, heads * dh)).reshape(b, t, heads, dh)
    gate = num.mm(h, wq[..., dh:].reshape(d, heads * dh)).reshape(b, t, heads, dh)
    k = num.mm(h, lp["wk"]).reshape(b, t, kv, dh)
    v = num.mm(h, lp["wv"]).reshape(b, t, kv, dh)
    q, k = num.rms(q, lp["g_q"], c["rms_norm_eps"]), num.rms(k, lp["g_k"], c["rms_norm_eps"])
    q, k = rotary(q, c["rope_theta"], first, turn), rotary(k, c["rope_theta"], first, turn)
    return q, gate, k, v


def _gated_out(num, lp, o, gate):
    b, t, heads, dh = o.shape
    return num.mm((o * jax.nn.sigmoid(gate)).reshape(b, t, heads * dh), lp["wo"])


def _rotates(c, i):
    return is_sliding(c, i) or c.get("full_rotary", False)


def _window(c, i):
    return c["sliding_window"] if is_sliding(c, i) else c.get("full_window")


def mask_of(c, i, t):
    """Layer ``i``'s mask and positions as two numbers, so that one compiled
    block serves every layer: the window (``t``, which hides nothing the causal
    mask shows, where the layer is full) and the multiplier of rotary's angles."""
    window = _window(c, i)
    return np.int32(t if window is None else window), np.float32(_rotates(c, i))


def _attention(num, c, lp, h, i):
    window, turn = mask_of(c, i, h.shape[1])
    q, gate, k, v = _project(num, c, lp, h, 0, turn)
    return _gated_out(num, lp, masked_attention(num, q, k, v, window), gate)


def route(num, c, lp, bias, h, forced=None):
    """Router logits (float32), the selection scores ``s + b`` over all
    experts, the top-k weights and the experts, for tokens ``h (N, D)``. With
    ``forced (N, k)`` those experts are taken in place of the top-k, each at
    its own score here (an entry below 0 leaves that choice free)."""
    r = num.mm(h, lp["wr"])
    s = jax.nn.sigmoid(r.astype(num.soft)).astype(jnp.float32)
    select = s + jax.lax.stop_gradient(bias)
    _, e = jax.lax.top_k(select, c["num_experts_per_tok"])
    if forced is not None:  # a negative entry: this file's own choice (one program for both cases)
        e = jnp.where(forced < 0, e, forced)
    w = jnp.take_along_axis(s, e, axis=-1).astype(num.soft)
    if c["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + jnp.asarray(1e-20, num.soft))
    w = w * jnp.asarray(c["route_scale"], num.soft)
    return r, s, select, w.astype(jnp.float32), e


def _swiglu(num, h, wg, wu, wd):
    return num.mm(jax.nn.silu(num.mm(h, wg)) * num.mm(h, wu), wd)


def _experts_of(num, c, lp, bias, h, forced=None):
    """h: (N, D), all tokens or a block of them. A loop over the held experts,
    each on all of these tokens with a zero weight where it was not chosen; the
    experts that are not held add nothing; the shared expert adds its output as
    it is. Returns the layer's output, the counts, the chosen experts, the
    selection scores, the weights, and what the auxiliary terms sum over
    tokens: the normalised scores an expert and ``logsumexp(r)^2``."""
    n, n_exp = h.shape[0], c["num_experts"]
    first, held = c.get("first_expert_held", 0), c["num_experts_held"]
    r, s, select, w, e = route(num, c, lp, bias, h, forced)
    dense_w = jnp.zeros((n, n_exp), jnp.float32).at[jnp.arange(n)[:, None], e].add(w)

    @jax.checkpoint
    def one(acc, ex):
        wg, wu, wd, w_e = ex
        return acc + w_e[:, None] * _swiglu(num, h, wg, wu, wd), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (lp["wg"], lp["wu"], lp["wd"], dense_w.T[first:first + held])
    )
    out = out + _swiglu(num, h, lp["ws_g"], lp["ws_u"], lp["ws_d"])
    counts = jnp.zeros((n_exp,), jnp.int32).at[e.reshape(-1)].add(1)
    p_sum = jnp.sum(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)
    z_sum = jnp.sum(jax.nn.logsumexp(r, axis=-1) ** 2)
    return out, counts, e, select, w, p_sum, z_sum


def _auxiliary(c, n, counts, p_sum, z_sum):
    f = counts.astype(jnp.float32) / (n * c["num_experts_per_tok"])
    return c["num_experts"] * jnp.sum(f * p_sum / n), z_sum / n


def _experts(num, c, lp, bias, h, forced=None):
    """The whole layer on ``h (N, D)``: its output, its auxiliary terms (over
    all experts), the counts, the chosen experts, the selection scores and the
    weights."""
    out, counts, e, select, w, p_sum, z_sum = _experts_of(num, c, lp, bias, h, forced)
    return (out, *_auxiliary(c, h.shape[0], counts, p_sum, z_sum), counts, e, select, w)


def _layer(num, c, dense, window, turn, lp, bias, x, forced):
    """A block (``dense``: its feed-forward a SwiGLU, else the experts; its
    mask ``window`` and ``turn``, :func:`mask_of`) on ``x (B, T, D)``. Everything that works a position at a
    time (norms, projections, the feed-forward or the experts) goes over blocks
    of ``TOKEN_BLOCK`` positions, each computed again in the backward pass, so
    that beside the attention's own blocks nothing of positions x 6,144 or of
    positions x heads x 2 x 128 is held for the whole sequence at once."""
    eps = c["rms_norm_eps"]
    b, t, d = x.shape
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    n = b * (t // block)
    split = lambda a: a.reshape((n, block) + a.shape[2:])  # noqa: E731
    join = lambda a: a.reshape((b, t) + a.shape[2:])  # noqa: E731
    firsts = jnp.tile(jnp.arange(0, t, block), b)

    @jax.checkpoint
    def before(args):
        first, xb = args
        return tuple(a[0] for a in _project(num, c, lp, num.rms(xb[None], lp["g_a"], eps), first, turn))

    q, gate, k, v = (join(a) for a in jax.lax.map(before, (firsts, split(x))))
    o = masked_attention(num, q, k, v, window)

    @jax.checkpoint
    def after(args):
        xb, ob, gb, forced_b = args
        xb = xb + num.rms(_gated_out(num, lp, ob[None], gb[None])[0], lp["g_b"], eps)
        h = num.rms(xb, lp["g_c"], eps)
        if dense:
            y, rest = _swiglu(num, h, lp["wf_g"], lp["wf_u"], lp["wf_d"]), ()
        else:
            y, *rest = _experts_of(num, c, lp, bias, h, forced_b)
        return xb + num.rms(y, lp["g_d"], eps), tuple(rest)

    if forced is None:  # every choice left free
        forced = jnp.full((b * t, c["num_experts_per_tok"]), -1, jnp.int32)
    x, rest = jax.lax.map(after, (split(x), split(o), split(gate), forced.reshape(n, block, -1)))
    if not rest:
        return join(x), []
    counts, e, select, w, p_sum, z_sum = rest
    counts = jnp.sum(counts, axis=0)
    flat = lambda a: a.reshape((b * t,) + a.shape[2:])  # noqa: E731
    return join(x), [*_auxiliary(c, b * t, counts, p_sum.sum(0), z_sum.sum()), counts, flat(e), flat(select), flat(w)]


def hidden_states(params, tokens, c, products="float32", forced=None):
    """Final-norm output (B, T, D), and per expert layer the auxiliary terms,
    the counts, the chosen experts (N, k), the selection scores (N, E) and the
    top-k weights. ``forced (expert layers, N, k)`` fixes every layer's experts."""
    num = _Numerics(products)
    x = _embedded(params["embed"], tokens, c)
    aux = []
    for i, lp in enumerate(params["layers"]):
        j = len(aux)
        dense = is_dense(c, i)
        x, rest = _layer(
            num, c, dense, *mask_of(c, i, tokens.shape[1]),
            lp, None if dense else params["bias"][j], x, None if dense or forced is None else forced[j],
        )
        if not dense:
            aux.append(rest)
    return num.rms(x, params["g_f"], c["rms_norm_eps"]), aux


def _embedded(embed, tokens, c):
    return embed[tokens] * np.float32(np.sqrt(c["hidden_size"]))


def logits_of(params, tokens, c, products="float32", last: int = 0, forced=None):
    num = _Numerics(products)
    h, aux = hidden_states(params, tokens, c, products, forced)
    return num.mm(h[:, -last:], params["head"]), aux


def loss_parts(params, tokens, c, coef, products="float32", forced=None, last: int = 0):
    """``(loss, parts)``: parts = ce, load_balance, router_z (means over the
    expert layers), expert_counts (expert layers x experts), chosen (x N x k),
    probs (x N x experts: the selection scores ``s + b``, what a choice is
    held against), weights (x N x k) and, with ``last``, ``last_logits`` (the
    logits of the last ``last`` positions of each sequence, from the same
    forward pass)."""
    num = _Numerics(products)
    h, aux = hidden_states(params, tokens, c, products, forced)
    ce = _cross_entropy(num, h, params["head"], tokens)
    loss, parts = _parts(ce, aux, coef)
    if last:
        parts["last_logits"] = jax.lax.stop_gradient(num.mm(h[:, -last:], params["head"]))
    return loss, parts


def _cross_entropy(num, h, head, tokens):
    """Mean next-token cross-entropy of the final-norm output ``h (B, T, D)``,
    ``TOKEN_BLOCK`` positions' logits at a time."""
    b, t = tokens.shape
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    split = lambda a: a.reshape((b * (t // block), block) + a.shape[2:])  # noqa: E731
    targets = jnp.roll(tokens, -1, axis=1)
    counted = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t))  # the last position has no next token

    @jax.checkpoint
    def one_block(args):  # logits of a block of positions only, and again in the backward pass
        hs, ys, keep = args
        logits = num.mm(hs, head)
        picked = jnp.take_along_axis(logits, ys[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(keep, jax.nn.logsumexp(logits, axis=-1) - picked, 0.0))

    return jnp.sum(jax.lax.map(one_block, (split(h), split(targets), split(counted)))) / (b * (t - 1))


def _parts(ce, aux, coef):
    """The loss and its parts from the cross-entropy and what each expert
    layer gave (:func:`_layer`)."""
    lb = jnp.mean(jnp.stack([a[0] for a in aux]))
    z = jnp.mean(jnp.stack([a[1] for a in aux]))
    return ce + coef["load_balance"] * lb + coef["router_z"] * z, {
        "ce": ce, "load_balance": lb, "router_z": z,
        "expert_counts": jnp.stack([a[2] for a in aux]),
        "chosen": jnp.stack([a[3] for a in aux]),
        "probs": jnp.stack([a[4] for a in aux]),
        "weights": jnp.stack([a[5] for a in aux]),
    }


# -- AdamW on the parameters, the rule on the biases --------------------------------


def _without_bias(tree):
    return {k: v for k, v in tree.items() if k != "bias"}


def adamw_init(params):
    """Zero moments **on the host** (numpy): beside 2.95 GB of parameters and
    as much of gradients, the float32 backward pass at 16,384 positions leaves
    no room on the chip for 5.9 GB of moments, so they come to the device for
    the update alone (``train_step``, ``update_gaps``) and go back."""
    zeros = lambda: jax.tree.map(lambda a: np.zeros(a.shape, np.float32), _without_bias(params))  # noqa: E731
    return {"m": zeros(), "v": zeros(), "count": np.zeros((), np.int32)}


def _to_host(state):
    """``state`` as numpy, its device buffers given back."""
    host = jax.tree.map(np.asarray, jax.device_get(state))
    for leaf in jax.tree.leaves(state):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
    return host


def adamw_update(params, grads, state, o, low_moments=False):
    """``olmoe_plain.adamw_update`` on everything but ``bias``: no moment, no
    decay and no share of the clip's norm for it."""
    new, state = olmoe_plain.adamw_update(_without_bias(params), _without_bias(grads), state, o, low_moments)
    return {**new, "bias": params["bias"]}, state


def bias_rule(bias, counts, rate):
    """``b_e += rate * sign(mean(c) - c_e)`` in every expert layer: ``bias``
    and ``counts`` (expert layers x experts)."""
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(counts, axis=-1, keepdims=True) - counts)


def update_gaps(params, grads, state, got, o):
    """``olmoe_plain.update_gaps`` on everything but ``bias`` (which AdamW never
    moves; the kind's ``bias_gap`` holds the rule to the step's own counts)."""
    bias = params["bias"]
    new, state, gaps = olmoe_plain._update_gaps(
        _without_bias(params), _without_bias(grads), state, None if got is None else _without_bias(got), _freeze(o)
    )
    return {**new, "bias": bias}, _to_host(state), gaps


# -- steps and evaluations ----------------------------------------------------------


_NOT_THE_MODEL = ("full_window", "full_rotary", "num_hidden_layers", "sliding_window", "global_attn_every_n_layers",
                  "num_dense_layers", "bias_rate")  # what a block's program does not read: the layers' order and masks


def _block_key(c):
    return _freeze({k: v for k, v in c.items() if k not in _NOT_THE_MODEL})


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _block_forward(key, products, dense, lp, bias, x, forced, window, turn):
    return _layer(_Numerics(products), _thaw(key), dense, window, turn, lp, bias, x, forced)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _block_backward(key, products, dense, lp, bias, x, forced, window, turn, cotangents):
    """The block again from its input, and ``cotangents`` (of its output and,
    for an expert block, of its two auxiliary terms) pulled back to its
    parameters and its input."""

    def block(lp, x):
        out, rest = _layer(_Numerics(products), _thaw(key), dense, window, turn, lp, bias, x, forced)
        return (out, *rest[:2])

    return jax.vjp(block, lp, x)[1](cotangents)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _head_backward(eps, products, last, g_f, head, x, tokens):
    """From the last block's output: the cross-entropy, the last ``last``
    positions' logits, and the cross-entropy's gradients by the final norm's
    gains, the head and that output."""
    num = _Numerics(products)

    def f(g_f, head, x):
        h = num.rms(x, g_f, eps)
        return _cross_entropy(num, h, head, tokens), jax.lax.stop_gradient(num.mm(h[:, -last:], head))

    return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(g_f, head, x)


@jax.jit
def _embed_backward(embed, tokens, d_x, scale):
    return jnp.zeros_like(embed).at[tokens].add(d_x * scale)


def _gradients(params, tokens, c, coef, products, forced):
    """Loss, parts (with the last ``LAST_LOGITS`` positions' logits) and every
    gradient, for a step and for an evaluation alike: backpropagation written
    out over the blocks, **a block a program** (forward: each block's input is
    kept; backward, from the head down: a block is computed again from its input
    and its cotangents pulled back). The arithmetic is that of ``jax.grad`` of
    :func:`loss_parts` (``tests/test_trinity.py`` holds the two together); what
    it buys is size. The same two programs serve every block of a kind (dense or
    experts; the mask and rotary's angles are arguments, :func:`mask_of`), so a
    run compiles two blocks where one program of the whole model held eight,
    each twice; a control of the mask or the positions compiles nothing; and a
    block's second forward pass cannot run before its cotangent has arrived (in
    one program XLA ran all eight first: 17.4 GB at 16,384 positions)."""
    key, t = _block_key(c), tokens.shape[1]
    tokens = jnp.asarray(tokens)
    blocks, inputs, aux = [], [], []
    x = _embedded(params["embed"], tokens, c)
    for i, lp in enumerate(params["layers"]):
        dense = is_dense(c, i)
        j = len(aux)
        blocks.append((key, products, dense, lp, None if dense else params["bias"][j], x,
                       None if dense else jnp.asarray(forced[j]), *mask_of(c, i, t)))
        x, rest = _block_forward(*blocks[-1])
        if not dense:
            aux.append(rest)
    last = min(LAST_LOGITS, t)
    (ce, last_logits), (d_gf, d_head, d_x) = _head_backward(
        c["rms_norm_eps"], products, last, params["g_f"], params["head"], x, tokens
    )
    loss, parts = _parts(ce, aux, coef)
    parts["last_logits"] = last_logits
    of_aux = tuple(jnp.float32(coef[name] / len(aux)) for name in ("load_balance", "router_z"))
    d_layers = []
    while blocks:
        block = blocks.pop()  # with it goes the last hold on this block's input
        d_lp, d_x = _block_backward(*block, (d_x,) if block[2] else (d_x, *of_aux))
        d_layers.append(d_lp)
    grads = {
        "embed": _embed_backward(params["embed"], tokens, d_x, np.float32(np.sqrt(c["hidden_size"]))),
        "g_f": d_gf, "head": d_head, "layers": d_layers[::-1], "bias": jnp.zeros_like(params["bias"]),
    }
    return loss, parts, grads


@functools.partial(jax.jit, static_argnums=(4, 5), donate_argnums=(0, 1, 2))
def _apply(params, grads, state, counts, o_items, rate):
    params, state = adamw_update(params, grads, state, _thaw(o_items))
    params["bias"] = bias_rule(params["bias"], counts, rate)
    return params, state


def _free_choice(c, tokens):
    """``forced`` with every entry left free."""
    return np.full((len(expert_layers(c)), tokens.shape[0] * tokens.shape[1], c["num_experts_per_tok"]), -1, np.int32)


def train_step(params, state, tokens, c, o, products="float32"):
    """One optimizer step and one move of the biases; ``params`` and ``state``
    are consumed, and ``state`` comes back on the host (``adamw_init``): the
    gradients, then the update with the moments beside them."""
    with jax.default_matmul_precision("highest"):
        loss, parts, grads = _gradients(params, tokens, c, o["coef"], products, _free_choice(c, tokens))
        params, state = _apply(params, grads, state, parts["expert_counts"], _freeze(o), c["bias_rate"])
    del parts["probs"], parts["last_logits"]  # not what a step is read for
    return params, _to_host(state), loss, parts


def evaluate(params, tokens, c, coef, last, products="float32", forced=None):
    """Loss, its parts, the gradient's norm per parameter group and the
    logits of the last ``last`` positions, at ``params``: the step's own
    program (``forced=None``: every entry left free), then the norms of its
    gradients."""
    if last > LAST_LOGITS:
        raise ValueError(f"the program gives the last {LAST_LOGITS} positions' logits, not {last}")
    with jax.default_matmul_precision("highest"):
        loss, parts, grads = _gradients(
            params, tokens, c, coef, products, _free_choice(c, tokens) if forced is None else forced
        )
        norms = _group_norms(grads)
    for leaf in jax.tree.leaves(grads):
        leaf.delete()
    return loss, parts, norms, parts.pop("last_logits")[:, -last:]


def group_norms(grads) -> dict:
    """L2 norm of the gradient over each parameter group of ``GROUPS`` (the
    biases have none)."""
    sq = dict.fromkeys(GROUPS, 0.0)
    for name in ("embed", "g_f", "head"):
        sq[group_of(name)] = sq[group_of(name)] + jnp.sum(grads[name] ** 2)
    for lp in grads["layers"]:
        for name, g in lp.items():
            sq[group_of(name)] = sq[group_of(name)] + jnp.sum(g**2)
    return {k: jnp.sqrt(v) for k, v in sq.items()}


_group_norms = jax.jit(group_norms)


# -- the windowed attention alone ---------------------------------------------------


def edge_probe(seed: int, t: int, heads: int, kv_heads: int, dh: int, window: int, scale: float = 2.0):
    """Inputs on which the window's far edge decides the output: every key a
    vector of +-1 of its own, every query ``scale`` x (the key ``window - 1``
    before it + the key ``window`` before it), all exact in bfloat16. The two
    carry a score of about ``scale * sqrt(dh)`` each where every other key's is
    noise of deviation ``scale * sqrt(2)``: with the right mask the first is
    seen and the second is not, and the output is the first's value; a window
    one short sees neither, one long sees both and halves the value. Positions
    before ``window`` take their pair from the sequence's end (keys the causal
    mask hides): they see noise alone. Returns q ``(1, T, heads, dh)``, k and v
    ``(1, T, kv_heads, dh)`` in float32."""
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)), 4242), 2)
    k = jnp.where(jax.random.bernoulli(keys[0], 0.5, (1, t, kv_heads, dh)), 1.0, -1.0).astype(jnp.float32)
    v = jax.random.normal(keys[1], (1, t, kv_heads, dh), jnp.float32)
    at = jnp.arange(t)
    pair = k[:, (at - (window - 1)) % t] + k[:, (at - window) % t]
    return scale * jnp.repeat(pair, heads // kv_heads, axis=2), k, v


@functools.partial(jax.jit, static_argnums=(3,))
def _attention_and_gradients(q, k, v, window, weights):
    num = _Numerics("float32")

    def f(q, k, v):
        out = masked_attention(num, q, k, v, window)
        return jnp.sum(out * weights), out

    (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (out,) + grads


def attention_and_gradients(q, k, v, window, weights):
    """The masked form of the windowed attention in float32 and the gradients
    of ``sum(out * weights)`` by q, k and v: ``(out, dq, dk, dv)``."""
    with jax.default_matmul_precision("highest"):
        return _attention_and_gradients(q, k, v, window, weights)
