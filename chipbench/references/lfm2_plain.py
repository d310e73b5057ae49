"""Plain reference for the ``lfm2_step`` kind: LFM2-24B-A2B (Liquid AI;
``config.json`` of ``LiquidAI/LFM2-24B-A2B``, ``model_type`` ``lfm2_moe``; HF
``modeling_lfm2_moe.py``) forward, loss, gradients, AdamW and the balance rule
of its routers' biases in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. It imports nothing of heat_tpu;
what is the same mathematics as in the other references it takes from them:
AdamW, the batches and the numbers of ``correct`` from ``olmoe_plain.py``; the
bias rule, the masked attention, rotary, the blocked cross-entropy and its
backward program, the update of parameters and biases from ``trinity_plain.py``.

    norm(x; w)  = x rsqrt(mean x^2 + eps) w                       plain RMSNorm, w starts at 1
    x  = Embed[tokens]                                            no scale
    block i:  x = x + mixer_i(norm(x; g_a));  x = x + ffn_i(norm(x; g_c))         two norms a block
    mixer_i, layer_types[first_block + i] == "conv":
      [B | C | xx] = h W_in  (thirds, in that order);  z = B * xx
      y_t = sum_{j < K} w[:, j] * z_{t - (K - 1) + j}             depthwise, causal, zeros before the first, no bias, no activation
      out = (C * y) W_out
    mixer_i, "full_attention" (H query heads on Hkv key-value heads of size D):
      q = h W_q;  k = h W_k;  v = h W_v;  q, k = norm over each head (g_q, g_k), then rotary (rotate-half over all of D, theta)
      softmax(q k^T / sqrt(D)) v, t sees every j <= t, key-value head j serving query heads j H/Hkv ..;  out = attn W_o
    ffn_i, i < num_dense_layers:  (silu(h Wf_g) * (h Wf_u)) Wf_d
    ffn_i, otherwise:  s = sigmoid(h W_r) over all E;  e_1..e_k = top-k of (s + b), b the layer's bias, no gradient
      w_j = s[e_j] / (sum_j s[e_j] + 1e-6) * routed_scaling_factor
      y = sum_{j: e_j held} w_j E_{e_j}(h),   E(h) = (silu(h Wg) * (h Wu)) Wd                     no shared expert
    logits = norm(x; g_f) Embed^T  (the head is the embedding table);   loss = CE(next token)
    after a step, in every expert layer:  b_e += bias_rate * sign(mean_e'(c_e') - c_e),  c the step's counts over all E

Departures from HF's model: (1) **the share**: this is one of ``num_experts /
num_experts_held`` ranks that divide every expert layer: the router, the bias,
the top-k and its normalisation are over all experts, but only experts
``first_expert_held .. + num_experts_held - 1`` have weights here, and what the
others would add is left out of the layer's result; the vocabulary is a slice,
which is a smaller vocabulary; the layers are the published blocks
``first_block .. first_block + num_hidden_layers - 1`` (``num_dense_layers`` of
them dense); (2) the two auxiliary terms stay defined as in ``trinity_plain``,
their coefficients 0; (3) the bias rule and its rate are assumed
(``config.json`` gives ``use_expert_bias`` only); (4) only so that it fits
beside its optimizer state: a block a program, each recomputed in the backward
pass, what goes a position at a time in blocks of ``TOKEN_BLOCK`` positions
(the convolution itself goes over the whole sequence: three float32 arrays of
positions x hidden), attention a head and a block of queries at a time, the
held experts a loop, the cross-entropy in blocks; (5) no padding, no cache,
no dropout, no document boundaries.

``products="bf16"`` is the **control** a precision below the configuration's:
bfloat16 operands *and* accumulator in every product, bfloat16 norms, gates,
taps, sigmoid and weights. Two more controls are keys of ``c`` that the
configuration does not have: ``conv_taps_used`` (a convolution that reads fewer
taps: the earliest dropped) and ``untied_head`` (the table's gradient without
the head's product, as if the head were a matrix of its own). ``correct`` must
refuse each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.olmoe_plain import (  # noqa: F401  (the kind reads these from here)
    _freeze, _normal, _thaw, batch, rel_gap, rms_gap, routing_disagreement, zipf_cdf,
)
from chipbench.references.trinity_plain import (  # noqa: F401
    LAST_LOGITS, TOKEN_BLOCK, _apply, _auxiliary, _cross_entropy, _embed_backward, _free_choice, _head_backward,
    _Numerics, _parts, _swiglu, _to_host, adamw_init, adamw_update, bias_rule, expert_layers, is_dense,
    masked_attention, rotary, update_gaps,
)

GROUPS = ("embed", "conv", "attention", "norms", "dense", "router", "experts")
WRITES_TO_STREAM = ("w_out", "wo", "wd", "wf_d")


# -- what a run is made from ------------------------------------------------------


def is_conv(c: dict, i: int) -> bool:
    return c["layer_types"][c.get("first_block", 0) + i] == "conv"


def param_shapes(c: dict) -> dict:
    d, v = c["hidden_size"], c["vocab_size"]
    e, held, f, wide = c["num_experts"], c["num_experts_held"], c["moe_intermediate_size"], c["intermediate_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    norms = {"g_a": (d,), "g_c": (d,)}
    conv = {"w_in": (d, 3 * d), "w_conv": (d, c["conv_L_cache"]), "w_out": (d, d)}
    attention = {"wq": (d, h * dh), "wk": (d, hkv * dh), "wv": (d, hkv * dh), "g_q": (dh,), "g_k": (dh,), "wo": (h * dh, d)}
    dense = {"wf_g": (d, wide), "wf_u": (d, wide), "wf_d": (wide, d)}
    moe = {"wr": (d, e), "wg": (held, d, f), "wu": (held, d, f), "wd": (held, f, d)}
    return {
        "embed": (v, d), "g_f": (d,),
        "layers": [
            {**norms, **(conv if is_conv(c, i) else attention), **(dense if is_dense(c, i) else moe)}
            for i in range(c["num_hidden_layers"])
        ],
    }


def group_of(name: str) -> str:
    if name.startswith("g_"):
        return "norms"
    if name == "embed":
        return "embed"
    if name == "wr":
        return "router"
    if name in ("wg", "wu", "wd"):
        return "experts"
    if name.startswith("wf_"):
        return "dense"
    return "conv" if name.startswith("w_") else "attention"


def init_params(seed: int, c: dict, std: float = 0.02, out_std=None) -> dict:
    """Float32, made on the device, leaf ``i`` (in the order of
    ``param_shapes``) from ``fold_in(PRNGKey(seed mod 2^31), i)``: matrices
    normal(0, std), those that write into the residual stream (``w_out``,
    ``wo`` and every down projection) normal(0, out_std), the taps uniform in
    +-1/sqrt(taps) (torch's own draw for a depthwise ``Conv1d``: a convolution
    that passes its input on at its own size), norm gains 1; ``bias`` (expert
    layers x experts) 0: it is no parameter, and rides in the tree beside them."""
    out_std = std if out_std is None else out_std
    shapes = param_shapes(c)
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.PRNGKey(seed % (2**31))
    out = []
    for i, (path, shape) in enumerate(paths):
        name = path[-1].key
        if name.startswith("g_"):
            out.append(jnp.ones(shape, jnp.float32))
        elif name == "w_conv":
            bound = float(shape[1]) ** -0.5
            out.append(jax.random.uniform(jax.random.fold_in(key, i), shape, jnp.float32, -bound, bound))
        else:
            out.append(_normal(jax.random.fold_in(key, i), shape, out_std if name in WRITES_TO_STREAM else std))
    params = jax.tree.unflatten(tree, out)
    params["bias"] = jnp.zeros((len(expert_layers(c)), c["num_experts"]), jnp.float32)
    return params


# -- the model --------------------------------------------------------------------


def short_conv(num, b, cc, xx, w, taps_used=None):
    """``C * conv(B * x)`` over ``(B, T, C)`` arrays with taps ``w (C, K)``:
    the shifted sum written out, position ``t`` reading ``t - (K - 1) .. t``.
    ``taps_used`` < K is the control: the earliest taps are not read."""
    taps, t = w.shape[1], xx.shape[1]
    if taps_used is not None:
        w = w * (jnp.arange(taps) >= taps - taps_used)
    soft = num.soft
    z = jnp.pad(b.astype(soft) * xx.astype(soft), ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(z[:, j:j + t] * w[:, j].astype(soft) for j in range(taps))
    return (cc.astype(soft) * y).astype(jnp.float32)


def route(num, c, lp, bias, h, forced=None):
    """Router logits (float32), the selection scores ``s + b`` over all
    experts, the top-k weights and the experts, for tokens ``h (N, D)``. With
    ``forced (N, k)`` those experts are taken in place of the top-k, each at
    its own score here (an entry below 0 leaves that choice free)."""
    r = num.mm(h, lp["wr"])
    s = jax.nn.sigmoid(r.astype(num.soft)).astype(jnp.float32)
    select = s + jax.lax.stop_gradient(bias)
    _, e = jax.lax.top_k(select, c["num_experts_per_tok"])
    if forced is not None:
        e = jnp.where(forced < 0, e, forced)
    w = jnp.take_along_axis(s, e, axis=-1).astype(num.soft)
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + jnp.asarray(1e-6, num.soft))
    w = w * jnp.asarray(c["routed_scaling_factor"], num.soft)
    return r, s, select, w.astype(jnp.float32), e


def _experts_of(num, c, lp, bias, h, forced=None):
    """h: (N, D), all tokens or a block of them. A loop over the held experts,
    each on all of these tokens with a zero weight where it was not chosen; the
    experts that are not held add nothing. Returns the layer's output, the
    counts, the chosen experts, the selection scores, the weights, and what the
    auxiliary terms sum over tokens."""
    n, n_exp = h.shape[0], c["num_experts"]
    first, held = c.get("first_expert_held", 0), c["num_experts_held"]
    r, s, select, w, e = route(num, c, lp, bias, h, forced)
    dense_w = jnp.zeros((n, n_exp), jnp.float32).at[jnp.arange(n)[:, None], e].add(w)

    @jax.checkpoint
    def one(acc, ex):
        wg, wu, wd, w_e = ex
        return acc + w_e[:, None] * _swiglu(num, h, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (lp["wg"], lp["wu"], lp["wd"], dense_w.T[first:first + held]))
    counts = jnp.zeros((n_exp,), jnp.int32).at[e.reshape(-1)].add(1)
    p_sum = jnp.sum(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)
    z_sum = jnp.sum(jax.nn.logsumexp(r, axis=-1) ** 2)
    return out, counts, e, select, w, p_sum, z_sum


def experts_layer(c, lp, bias, h, products="float32"):
    """One expert layer's result on ``h (N, D)`` for the share that ``c``
    states (``first_expert_held``, ``num_experts_held``), and its counts: what
    the share test adds up over the shares."""
    out, counts, *_ = _experts_of(_Numerics(products), c, lp, bias, h)
    return out, counts


def _layer(num, c, lp, bias, x, forced):
    """A block on ``x (B, T, D)``; its kind is read from its parameters (a
    convolution's ``w_in`` or an attention's ``wq``; a dense feed-forward's
    ``wf_g`` or a router's ``wr``). What works a position at a time goes over
    blocks of ``TOKEN_BLOCK`` positions, each computed again in the backward
    pass; the convolution and the attention see the whole sequence."""
    eps = c["norm_eps"]
    b, t, d = x.shape
    conv, dense = "w_in" in lp, "wf_g" in lp
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    n = b * (t // block)
    split = lambda a: a.reshape((n, block) + a.shape[2:])  # noqa: E731
    join = lambda a: a.reshape((b, t) + a.shape[2:])  # noqa: E731
    firsts = jnp.tile(jnp.arange(0, t, block), b)
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // heads

    @jax.checkpoint
    def before(args):
        first, xb = args
        h = num.rms(xb, lp["g_a"], eps)
        if conv:
            return tuple(num.mm(h, lp["w_in"][:, i * d:(i + 1) * d]) for i in range(3))
        q = num.rms(num.mm(h, lp["wq"]).reshape(1, block, heads, dh), lp["g_q"], eps)
        k = num.rms(num.mm(h, lp["wk"]).reshape(1, block, kv, dh), lp["g_k"], eps)
        theta = c["rope_parameters"]["rope_theta"]
        return rotary(q, theta, first)[0], rotary(k, theta, first)[0], num.mm(h, lp["wv"]).reshape(block, kv, dh)

    parts = tuple(join(a) for a in jax.lax.map(before, (firsts, split(x))))
    if conv:
        mixed = short_conv(num, *parts, lp["w_conv"], c.get("conv_taps_used"))
    else:
        mixed = masked_attention(num, *parts, np.int32(t)).reshape(b, t, heads * dh)

    @jax.checkpoint
    def after(args):
        xb, mb, forced_b = args
        xb = xb + num.mm(mb, lp["w_out"] if conv else lp["wo"])
        h = num.rms(xb, lp["g_c"], eps)
        if dense:
            y, rest = _swiglu(num, h, lp["wf_g"], lp["wf_u"], lp["wf_d"]), ()
        else:
            y, *rest = _experts_of(num, c, lp, bias, h, forced_b)
        return xb + y, tuple(rest)

    if forced is None:  # every choice left free
        forced = jnp.full((b * t, c["num_experts_per_tok"]), -1, jnp.int32)
    x, rest = jax.lax.map(after, (split(x), split(mixed), forced.reshape(n, block, -1)))
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
    x = params["embed"][tokens]
    aux = []
    for i, lp in enumerate(params["layers"]):
        j, dense = len(aux), is_dense(c, i)
        x, rest = _layer(num, c, lp, None if dense else params["bias"][j], x, None if dense or forced is None else forced[j])
        if not dense:
            aux.append(rest)
    return num.rms(x, params["g_f"], c["norm_eps"]), aux


def logits_of(params, tokens, c, products="float32", last: int = 0, forced=None):
    h, aux = hidden_states(params, tokens, c, products, forced)
    return _Numerics(products).mm(h[:, -last:], params["embed"].T), aux


def loss_parts(params, tokens, c, coef, products="float32", forced=None):
    """``(loss, parts)`` as ``trinity_plain.loss_parts`` gives them, the head
    the embedding table: autodiff of this is what :func:`_gradients` writes out."""
    h, aux = hidden_states(params, tokens, c, products, forced)
    return _parts(_cross_entropy(_Numerics(products), h, params["embed"].T, tokens), aux, coef)


# -- steps and evaluations ----------------------------------------------------------


_NOT_THE_MODEL = ("num_hidden_layers", "num_dense_layers", "bias_rate", "untied_head")  # what a block's program does not read


def _block_key(c):
    # a block's kind is in its parameters, not in the list of the layers' types
    return _freeze({**{k: v for k, v in c.items() if k not in _NOT_THE_MODEL}, "layer_types": None})


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block_forward(key, products, lp, bias, x, forced):
    return _layer(_Numerics(products), _thaw(key), lp, bias, x, forced)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block_backward(key, products, lp, bias, x, forced, cotangents):
    """The block again from its input, and ``cotangents`` (of its output and,
    for an expert block, of its two auxiliary terms) pulled back to its
    parameters and its input."""

    def block(lp, x):
        out, rest = _layer(_Numerics(products), _thaw(key), lp, bias, x, forced)
        return (out, *rest[:2])

    return jax.vjp(block, lp, x)[1](cotangents)


def _gradients(params, tokens, c, coef, products, forced):
    """Loss, parts (with the last ``LAST_LOGITS`` positions' logits) and every
    gradient: backpropagation written out over the blocks, a block a program
    (``trinity_plain._gradients``' scheme; ``tests/test_lfm2.py`` holds it to
    ``jax.grad`` of :func:`loss_parts`). The head is the table: its gradient is
    the gather's rows plus the head's product (``untied_head``, the control,
    leaves the second out)."""
    key = _block_key(c)
    tokens = jnp.asarray(tokens)
    blocks, aux = [], []
    x = params["embed"][tokens]
    for i, lp in enumerate(params["layers"]):
        dense = is_dense(c, i)
        j = len(aux)
        blocks.append((key, products, lp, None if dense else params["bias"][j], x, None if dense else jnp.asarray(forced[j])))
        x, rest = _block_forward(*blocks[-1])
        if not dense:
            aux.append(rest)
    last = min(LAST_LOGITS, tokens.shape[1])
    (ce, last_logits), (d_gf, d_head, d_x) = _head_backward(
        c["norm_eps"], products, last, params["g_f"], params["embed"].T, x, tokens
    )
    loss, parts = _parts(ce, aux, coef)
    parts["last_logits"] = last_logits
    of_aux = tuple(jnp.float32(coef[name] / len(aux)) for name in ("load_balance", "router_z"))
    d_layers = []
    while blocks:
        block = blocks.pop()  # with it goes the last hold on this block's input
        d_lp, d_x = _block_backward(*block, (d_x,) if "wf_g" in block[2] else (d_x, *of_aux))
        d_layers.append(d_lp)
    d_embed = _embed_backward(params["embed"], tokens, d_x, np.float32(1.0))
    if not c.get("untied_head", False):
        d_embed = d_embed + d_head.T
    grads = {"embed": d_embed, "g_f": d_gf, "layers": d_layers[::-1], "bias": jnp.zeros_like(params["bias"])}
    return loss, parts, grads


def train_step(params, state, tokens, c, o, products="float32"):
    """One optimizer step and one move of the biases; ``params`` and ``state``
    are consumed, and ``state`` comes back on the host (``adamw_init``)."""
    with jax.default_matmul_precision("highest"):
        loss, parts, grads = _gradients(params, tokens, c, o["coef"], products, _free_choice(c, tokens))
        params, state = _apply(params, grads, state, parts["expert_counts"], _freeze(o), c["bias_rate"])
    del parts["probs"], parts["last_logits"]  # not what a step is read for
    return params, _to_host(state), loss, parts


def evaluate(params, tokens, c, coef, last, products="float32", forced=None):
    """Loss, its parts, the gradient's norm per parameter group and the
    logits of the last ``last`` positions, at ``params``."""
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
    for name in ("embed", "g_f"):
        sq[group_of(name)] = sq[group_of(name)] + jnp.sum(grads[name] ** 2)
    for lp in grads["layers"]:
        for name, g in lp.items():
            sq[group_of(name)] = sq[group_of(name)] + jnp.sum(g**2)
    return {k: jnp.sqrt(v) for k, v in sq.items()}


_group_norms = jax.jit(group_norms)


# -- the gated convolution alone ----------------------------------------------------


@functools.partial(jax.jit, static_argnums=(5,))
def _conv_and_gradients(b, cc, xx, w, weights, taps_used):
    num = _Numerics("float32")

    def f(b, cc, xx, w):
        out = short_conv(num, b, cc, xx, w, taps_used)
        return jnp.sum(out * weights), out

    (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)(b, cc, xx, w)
    return (out,) + grads


def conv_and_gradients(b, cc, xx, w, weights, taps_used=None):
    """The gated short convolution in float32 and the gradients of ``sum(out *
    weights)`` by B, C, x and the taps: ``(out, dB, dC, dx, dw)``."""
    return _conv_and_gradients(b, cc, xx, w, weights, taps_used)


# -- one update, entry by entry -------------------------------------------------------


@jax.jit
def _leaf_look(old, new, got, gradient):
    size = jnp.abs(gradient) / jnp.maximum(jnp.sqrt(jnp.mean(gradient * gradient)), 1e-30)
    step, miss = new - old, got - new
    turned = jnp.abs(miss) > 0.5 * jnp.abs(step)
    among = jnp.where(turned, size, jnp.nan)
    return {
        "turned_share": jnp.mean(turned),
        "sign_turned_share": jnp.mean(jnp.sign(got - old) != jnp.sign(step)),
        "turned_median_gradient": jnp.nanmedian(among),
        "turned_largest_gradient": jnp.nanmax(among),
    }


def leaf_look(old, new, got, gradient) -> dict:
    """One leaf's update entry by entry: ``old`` the leaf before it, ``new``
    this file's AdamW's, ``got`` another optimizer's of the same leaf,
    ``gradient`` the one this file's AdamW took. ``turned_share``: the entries
    where ``got`` misses ``new`` by more than half the update;
    ``sign_turned_share``: those moved the other way; and the median and the
    largest gradient among the turned, in root mean squares of the leaf's
    (None where none is turned): what says whether the two optimizers
    differ, or the gradients they were given."""
    found = {k: float(v) for k, v in _leaf_look(old, new, got, gradient).items()}
    return {k: None if v != v else v for k, v in found.items()}


def routed(tree) -> set:
    """The paths (layer, name) of the leaves of an expert layer: its router,
    its experts and the gain of the norm they read. Their gradient goes through
    the layer's own top-k choice."""
    return {(i, name) for i, lp in enumerate(tree["layers"]) if "wr" in lp for name in ("wr", "wg", "wu", "wd", "g_c")}
