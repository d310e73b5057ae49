"""Plain reference for the ``glm_step`` kind: GLM-4.7-Flash (Z.ai, 30B-A3B;
``config.json`` of ``zai-org/GLM-4.7-Flash``, ``model_type`` ``glm4_moe_lite``;
the layers of HF ``modeling_deepseek_v3.py``, from which it derives; the
prediction module of DeepSeek-V3's report, section 2.2, in the release's weight
layout) forward, both losses, gradients, AdamW and the balance rule of its
routers' biases in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. It imports nothing of heat_tpu;
what is the same mathematics as in the other references it takes from them:
AdamW, the batches and the numbers of ``correct`` from ``olmoe_plain.py``; the
expert layer (sigmoid scores, a selection bias, the top-k normalised over its
sum + 1e-20 and scaled, an ungated shared expert, the held share), the bias
rule, the masked attention, rotary, the blocked cross-entropy and its backward
program, the update of parameters and biases from ``trinity_plain.py``; the look
at one leaf's update from ``lfm2_plain.py``.

    norm(x; w)  = x rsqrt(mean x^2 + eps) w                       plain RMSNorm, w starts at 1
    x  = Embed[tokens]
    block i:  x = x + attn_i(norm(x; g_a));  x = x + ffn_i(norm(x; g_c))          two norms a block
    attn (H heads; a head's query and key: nope without positions | rope with; its value v = nope + rope wide):
      c_q = norm(u W_qa; g_qa);   [q_nope | q_rope] = c_q W_qb             a head at a time
      [c_kv | k_r] = u W_kva;   c_kv = norm(c_kv; g_kva);   [k_nope | v] = c_kv W_kvb
      q_rope, k_r = rotary (rotate-half over rope, theta);  k_r is one vector a position: every head's key ends in it
      softmax([q_nope | q_rope] [k_nope | k_r]^T / sqrt(nope + rope)) v,  t sees every j <= t;   out = heads W_o
    ffn_i, i < first_k_dense_replace:  (silu(h Wf_g) * (h Wf_u)) Wf_d
    ffn_i, otherwise:  trinity_plain's expert layer: s = sigmoid(h W_r) over all E;  top-k of (s + b);
      w_j = s[e_j] / (sum_j s[e_j] + 1e-20) * routed_scaling_factor;  y = sum_{j: e_j held} w_j E_{e_j}(h) + E_shared(h)
    logits = norm(x_L; g_f) W_head;   ce = CE(logits_i, t_{i+1}) over i <= T - 2
    the prediction module, over the T - 1 positions that have a next token:
      m_i = [norm(Embed[t_{i+1}]; g_e) | norm(x_L,i; g_h)] W_eh          x_L: the stream after the last block, before g_f
      m = block_L(m)                                                     one more whole block, the last of ``layers``
      logits'_i = norm(m_i; g_s) W_head                                  the trunk's Embed and W_head
      ce_mtp = CE(logits'_i, t_{i+2}) over i <= T - 3
    loss = ce + coef.mtp * ce_mtp  (+ c_lb * load balance + c_z * router z, both coefficients 0)
    after a step, in every expert layer (the module's the last):  b_e += bias_rate * sign(mean_e'(c_e') - c_e)

Departures from HF's model: (1) **the share**, as ``trinity_plain`` states it
(experts ``first_expert_held .. + num_experts_held - 1`` of ``n_routed_experts``
have weights here; the vocabulary is a slice); (2) the prediction module is in
HF's file only as weights that it skips: its order ``[embedding | stream]``, the
stream taken before the final norm and the weight of its loss are assumed (the
configuration's ``assumed``); (3) the auxiliary terms and the bias rule as in
``trinity_plain``; (4) only so that it fits beside its optimizer state: a block a
program, each recomputed in the backward pass, what goes a position at a time in
blocks of ``TOKEN_BLOCK`` positions, attention a head and a block of queries at
a time, the held experts a loop, the cross-entropy in blocks; (5) only so that
the module's block is the program the trunk's expert blocks already compiled
(on the chip a block's two programs cost more than a minute to compile, at
each of two batch shapes): its ``T - 1`` positions go through the block as a
sequence of ``T`` whose last position is a row of zeros, which attention being
causal no position sees and whose output, cut off again, takes no cotangent;
its choice of experts is taken out of the layer's counts, which are of the
``T - 1``, and where the layer's per-token outputs stand beside the other
layers' (``chosen``, ``probs``, ``weights``) that position holds equal scores
(no choice disagrees with them) and no weight; the two auxiliary terms, whose
coefficients are 0, count the row; (6) no cache, no dropout, no document
boundaries.

``products="bf16"`` is the **control** a precision below the configuration's.
Further controls are keys of ``c`` that the configuration does not have:
``rope_all`` (rotary over all of a head's query and key, not its rope part),
``own_rope_key`` (a rotary key of its own for each head: head ``i`` takes
``k_r`` with its features rolled by ``i``), ``no_kv_norm`` (the latent norm of
keys and values left out), ``mtp_shift`` (the module fed ``Embed[t_{i +
mtp_shift}]``: 0 is the token itself, 2 one too far, which leaks), and the
changed keys ``routed_scaling_factor``, ``coef.mtp`` and ``bias_rate`` (0: biases
left alone). ``correct`` must refuse each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.lfm2_plain import leaf_look, routed  # noqa: F401  (the kind reads these from here)
from chipbench.references.olmoe_plain import (  # noqa: F401
    _freeze, _normal, _thaw, batch, rel_gap, rms_gap, routing_disagreement, zipf_cdf,
)
from chipbench.references.trinity_plain import (  # noqa: F401
    LAST_LOGITS, TOKEN_BLOCK, _apply, _auxiliary, _cross_entropy, _embed_backward, _experts_of, _head_backward,
    _Numerics, _parts, _swiglu, _to_host, adamw_init, adamw_update, bias_rule, masked_attention, rotary, update_gaps,
)

GROUPS = ("embed", "attention", "norms", "dense", "router", "experts", "shared", "head", "merge")
WRITES_TO_STREAM = ("wo", "wd", "ws_d", "wf_d", "w_eh")
MODULE = ("g_e", "g_h", "g_s", "w_eh")  # the prediction module's own leaves, beside its block (the last of ``layers``)


# -- what a run is made from ------------------------------------------------------


def is_dense(c: dict, i: int) -> bool:
    return i < c["first_k_dense_replace"]


def blocks_of(c: dict) -> int:
    """The trunk's blocks and the module's one."""
    if c["num_nextn_predict_layers"] != 1:
        raise ValueError("this reference writes out one prediction module")
    return c["num_hidden_layers"] + 1


def expert_layers(c: dict):
    return [i for i in range(blocks_of(c)) if not is_dense(c, i)]


def _routing(c: dict) -> dict:
    """``c`` under the names ``trinity_plain``'s expert layer reads."""
    return {
        **c, "num_experts": c["n_routed_experts"], "route_norm": c["norm_topk_prob"],
        "route_scale": c["routed_scaling_factor"],
    }


def param_shapes(c: dict) -> dict:
    d, v = c["hidden_size"], c["vocab_size"]
    held, f, wide = c["num_experts_held"], c["moe_intermediate_size"], c["intermediate_size"]
    h, qr, kvr = c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    attention = {
        "g_a": (d,), "g_c": (d,), "wq_a": (d, qr), "g_qa": (qr,), "wq_b": (qr, h * (nope + rope)),
        "wkv_a": (d, kvr + rope), "g_kva": (kvr,), "wkv_b": (kvr, h * (nope + vd)), "wo": (h * vd, d),
    }
    dense = {"wf_g": (d, wide), "wf_u": (d, wide), "wf_d": (wide, d)}
    shared = f * c["n_shared_experts"]
    moe = {
        "wr": (d, c["n_routed_experts"]), "wg": (held, d, f), "wu": (held, d, f), "wd": (held, f, d),
        "ws_g": (d, shared), "ws_u": (d, shared), "ws_d": (shared, d),
    }
    return {
        "embed": (v, d), "g_f": (d,), "head": (d, v), "g_e": (d,), "g_h": (d,), "g_s": (d,), "w_eh": (2 * d, d),
        "layers": [{**attention, **(dense if is_dense(c, i) else moe)} for i in range(blocks_of(c))],
    }


def group_of(name: str) -> str:
    if name.startswith("g_"):
        return "norms"
    if name in ("embed", "head"):
        return name
    if name == "w_eh":
        return "merge"
    if name == "wr":
        return "router"
    if name in ("wg", "wu", "wd"):
        return "experts"
    if name.startswith("ws_"):
        return "shared"
    return "dense" if name.startswith("wf_") else "attention"


def init_params(seed: int, c: dict, std: float = 0.02, out_std=None, router_std=None) -> dict:
    """Float32, made on the device, leaf ``i`` (in the order of
    ``param_shapes``) from ``fold_in(PRNGKey(seed mod 2^31), i)``: matrices
    normal(0, std), those that write into the residual stream (``wo``, every
    down projection and ``w_eh``) normal(0, out_std), the routers normal(0,
    router_std) (None: std), norm gains 1; ``bias``
    (expert layers x experts, the module's last) 0: it is no parameter, and
    rides in the tree beside them."""
    out_std = std if out_std is None else out_std
    of = {"wr": std if router_std is None else router_std, **dict.fromkeys(WRITES_TO_STREAM, out_std)}
    shapes = param_shapes(c)
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.PRNGKey(seed % (2**31))
    out = []
    for i, (path, shape) in enumerate(paths):
        name = path[-1].key
        if name.startswith("g_"):
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(_normal(jax.random.fold_in(key, i), shape, of.get(name, std)))
    params = jax.tree.unflatten(tree, out)
    params["bias"] = jnp.zeros((len(expert_layers(c)), c["n_routed_experts"]), jnp.float32)
    return params


# -- the model --------------------------------------------------------------------


def latent_rows(num, c, lp, h, first=0):
    """The rows the attention core takes, from the normed input ``h (B, T',
    D)`` of positions ``first ..``: q and k ``(B, T', H, nope + rope)``, v
    ``(B, T', H, v)``, written out as the module docstring has them."""
    b, t, _ = h.shape
    heads, kvr = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    q = num.mm(num.rms(num.mm(h, lp["wq_a"]), lp["g_qa"], eps), lp["wq_b"]).reshape(b, t, heads, nope + rope)
    down = num.mm(h, lp["wkv_a"])
    c_kv, k_r = down[..., :kvr], down[..., kvr:]
    if not c.get("no_kv_norm", False):
        c_kv = num.rms(c_kv, lp["g_kva"], eps)
    kv = num.mm(c_kv, lp["wkv_b"]).reshape(b, t, heads, nope + vd)
    if c.get("own_rope_key", False):  # the control: every head another key
        k_r = jnp.stack([jnp.roll(k_r, i, axis=-1) for i in range(heads)], axis=2)
    else:
        k_r = jnp.broadcast_to(k_r[:, :, None, :], (b, t, heads, rope))
    if c.get("rope_all", False):  # the control: positions on all of a head
        q = rotary(q, theta, first)
        k = rotary(jnp.concatenate([kv[..., :nope], k_r], axis=-1), theta, first)
    else:
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta, first)], axis=-1)
        k = jnp.concatenate([kv[..., :nope], rotary(k_r, theta, first)], axis=-1)
    return q, k, kv[..., nope:]


def latent_attention(num, c, lp, h):
    """The whole mixer on its normed input ``h (B, T, D)``."""
    b, t, _ = h.shape
    q, k, v = latent_rows(num, c, lp, h)
    return num.mm(masked_attention(num, q, k, v, np.int32(t)).reshape(b, t, -1), lp["wo"])


def experts_layer(c, lp, bias, h, products="float32"):
    """One expert layer's result on ``h (N, D)`` for the share that ``c``
    states (``first_expert_held``, ``num_experts_held``), the shared expert in
    it, and its counts: what the share test adds up over the shares."""
    out, counts, *_ = _experts_of(_Numerics(products), _routing(c), lp, bias, h)
    return out, counts


def _layer(num, c, lp, bias, x, forced):
    """A block on ``x (B, T, D)``; its feed-forward is read from its
    parameters (a dense one's ``wf_g`` or a router's ``wr``). What works a
    position at a time goes over blocks of ``TOKEN_BLOCK`` positions, each
    computed again in the backward pass; the attention sees the whole sequence."""
    eps = c["rms_norm_eps"]
    b, t, d = x.shape
    dense = "wf_g" in lp
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    n = b * (t // block)
    split = lambda a: a.reshape((n, block) + a.shape[2:])  # noqa: E731
    join = lambda a: a.reshape((b, t) + a.shape[2:])  # noqa: E731
    firsts = jnp.tile(jnp.arange(0, t, block), b)

    @jax.checkpoint
    def before(args):
        first, xb = args
        return tuple(a[0] for a in latent_rows(num, c, lp, num.rms(xb[None], lp["g_a"], eps), first))

    q, k, v = (join(a) for a in jax.lax.map(before, (firsts, split(x))))
    mixed = masked_attention(num, q, k, v, np.int32(t)).reshape(b, t, -1)

    @jax.checkpoint
    def after(args):
        xb, mb, forced_b = args
        xb = xb + num.mm(mb, lp["wo"])
        h = num.rms(xb, lp["g_c"], eps)
        if dense:
            y, rest = _swiglu(num, h, lp["wf_g"], lp["wf_u"], lp["wf_d"]), ()
        else:
            y, *rest = _experts_of(num, _routing(c), lp, bias, h, forced_b)
        return xb + y, tuple(rest)

    if forced is None:  # every choice left free
        forced = jnp.full((b * t, c["num_experts_per_tok"]), -1, jnp.int32)
    x, rest = jax.lax.map(after, (split(x), split(mixed), forced.reshape(n, block, -1)))
    if not rest:
        return join(x), []
    counts, e, select, w, p_sum, z_sum = rest
    counts = jnp.sum(counts, axis=0)
    flat = lambda a: a.reshape((b * t,) + a.shape[2:])  # noqa: E731
    return join(x), [
        *_auxiliary(_routing(c), b * t, counts, p_sum.sum(0), z_sum.sum()), counts, flat(e), flat(select), flat(w)
    ]


def merged(num, c, g_e, g_h, w_eh, embed, x, tokens):
    """The module's input over the ``T - 1`` positions that have a next token:
    ``[norm(Embed[t_{i+1}]) | norm(x_i)] W_eh`` (``mtp_shift``, the control,
    feeds another token's embedding)."""
    t, eps = tokens.shape[1], c["rms_norm_eps"]
    ahead = jnp.roll(tokens, -c.get("mtp_shift", 1), axis=1)[:, :t - 1]
    return num.mm(jnp.concatenate([num.rms(embed[ahead], g_e, eps), num.rms(x[:, :t - 1], g_h, eps)], axis=-1), w_eh)


def _through_block(block, m, *rest):
    """``block`` on the module's ``T - 1`` positions ``m`` as a sequence of ``T``
    whose last position is zeros (what ``block`` returns beside its output
    comes back as it is)."""
    out, more = block(jnp.pad(m, ((0, 0), (0, 1), (0, 0))), *rest)
    return out[:, :-1], more


def _of_module(rest, b, t):
    """The module's layer's outputs with the row of zeros taken out: its
    choice out of the counts; where the per-token outputs stand beside the
    other layers', equal scores at that position and no weight."""
    lb, z, counts, e, select, w = rest
    last = np.arange(b * t) % t == t - 1  # static: the sequences' last positions
    counts = counts - jnp.zeros_like(counts).at[e[last].reshape(-1)].add(1)
    return [lb, z, counts, e, jnp.where(last[:, None], 1.0, select), jnp.where(last[:, None], 0.0, w)]


def hidden_states(params, tokens, c, products="float32", forced=None):
    """The final norm's output ``(B, T, D)``, the module's head norm's output
    ``(B, T - 1, D)``, and per expert layer (the module's last) the auxiliary
    terms, the counts, the chosen experts (N, k), the selection scores (N, E)
    and the top-k weights. ``forced (expert layers, N, k)`` fixes every
    layer's experts."""
    num = _Numerics(products)
    b, t = tokens.shape
    layers = params["layers"]
    x = params["embed"][tokens]
    aux = []
    for i, lp in enumerate(layers[:-1]):
        j, dense = len(aux), is_dense(c, i)
        x, rest = _layer(num, c, lp, None if dense else params["bias"][j], x, None if dense or forced is None else forced[j])
        if not dense:
            aux.append(rest)
    j = len(aux)
    m = merged(num, c, params["g_e"], params["g_h"], params["w_eh"], params["embed"], x, tokens)
    m, rest = _through_block(
        lambda m: _layer(num, c, layers[-1], params["bias"][j], m, None if forced is None else forced[j]), m
    )
    aux.append(_of_module(rest, b, t))
    eps = c["rms_norm_eps"]
    return num.rms(x, params["g_f"], eps), num.rms(m, params["g_s"], eps), aux


def logits_of(params, tokens, c, products="float32", last: int = 0, forced=None):
    """The trunk's logits of the last ``last`` positions, and the module's of
    its last ``last`` (positions ``T - 1 - last .. T - 2``)."""
    num = _Numerics(products)
    h, m, aux = hidden_states(params, tokens, c, products, forced)
    return num.mm(h[:, -last:], params["head"]), num.mm(m[:, -last:], params["head"]), aux


def loss_parts(params, tokens, c, coef, products="float32", forced=None):
    """``(loss, parts)`` as ``trinity_plain.loss_parts`` gives them, with
    ``ce_mtp`` among the parts and ``coef["mtp"]`` x it in the loss: autodiff
    of this is what :func:`_gradients` writes out."""
    num = _Numerics(products)
    h, m, aux = hidden_states(params, tokens, c, products, forced)
    loss, parts = _parts(_cross_entropy(num, h, params["head"], tokens), aux, coef)
    parts["ce_mtp"] = _cross_entropy(num, m, params["head"], tokens[:, 1:])  # position i against token i + 2
    return loss + coef["mtp"] * parts["ce_mtp"], parts


# -- steps and evaluations ----------------------------------------------------------


# what a block's program does not read
_NOT_THE_MODEL = ("num_hidden_layers", "first_k_dense_replace", "num_nextn_predict_layers", "bias_rate", "mtp_shift", "vocab_size")


def _block_key(c):
    return _freeze({k: v for k, v in c.items() if k not in _NOT_THE_MODEL})


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


@functools.partial(jax.jit, static_argnums=(0, 1))
def _merge_forward(key, products, leaves, embed, x, tokens):
    return merged(_Numerics(products), _thaw(key), *leaves, embed, x, tokens)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _merge_backward(key, products, leaves, embed, x, tokens, cotangent):
    """The merge again, and its output's cotangent pulled back to ``(g_e,
    g_h, w_eh)``, the table and the stream."""
    merge = lambda leaves, embed, x: merged(_Numerics(products), _thaw(key), *leaves, embed, x, tokens)  # noqa: E731
    return jax.vjp(merge, leaves, embed, x)[1](cotangent)


def _gradients(params, tokens, c, coef, products, forced):
    """Loss, parts (with the trunk's and the module's last ``LAST_LOGITS``
    positions' logits) and every gradient: backpropagation written out over
    the blocks, a block a program (``trinity_plain._gradients``' scheme;
    ``tests/test_glm.py`` holds it to ``jax.grad`` of :func:`loss_parts`). The
    head's gradient is the sum of its two uses, the table's of its gather's
    rows in the trunk and in the module's merge, the stream's after the last
    block of the head's and the merge's."""
    key, mkey = _block_key(c), _freeze({k: c.get(k, 1) for k in ("rms_norm_eps", "mtp_shift")})
    tokens = jnp.asarray(tokens)
    b, t = tokens.shape
    layers = params["layers"]
    blocks, aux = [], []
    x = params["embed"][tokens]
    for i, lp in enumerate(layers[:-1]):
        dense = is_dense(c, i)
        j = len(aux)
        blocks.append((key, products, lp, None if dense else params["bias"][j], x, None if dense else jnp.asarray(forced[j])))
        x, rest = _block_forward(*blocks[-1])
        if not dense:
            aux.append(rest)
    eps, last = c["rms_norm_eps"], min(LAST_LOGITS, t - 1)
    (ce, last_logits), (d_gf, d_head, d_x) = _head_backward(eps, products, last, params["g_f"], params["head"], x, tokens)
    merge = (mkey, products, tuple(params[n] for n in ("g_e", "g_h", "w_eh")), params["embed"], x, tokens)
    module = (key, products, layers[-1], params["bias"][len(aux)])
    m_in, of_module = _merge_forward(*merge), jnp.asarray(forced[len(aux)])
    m, rest = _through_block(lambda m: _block_forward(*module, m, of_module), m_in)
    aux.append(_of_module(rest, b, t))
    (ce_mtp, mtp_logits), (d_gs, d_head_mtp, d_m) = _head_backward(
        eps, products, last, params["g_s"], params["head"], m, tokens[:, 1:]
    )
    loss, parts = _parts(ce, aux, coef)
    weight = jnp.float32(coef["mtp"])
    loss = loss + weight * ce_mtp
    parts.update(ce_mtp=ce_mtp, last_logits=last_logits, mtp_logits=mtp_logits)
    of_aux = tuple(jnp.float32(coef[name] / len(aux)) for name in ("load_balance", "router_z"))
    d_m, d_module = _through_block(  # the row of zeros takes no cotangent and gives none back
        lambda m, d_m: _block_backward(*module, m, of_module, (d_m, *of_aux))[::-1], m_in, jnp.pad(weight * d_m, ((0, 0), (0, 1), (0, 0)))
    )
    (d_ge, d_gh, d_weh), d_embed, d_stream = _merge_backward(*merge, d_m)
    del m_in, merge
    d_x = d_x + d_stream
    d_layers = [d_module]
    while blocks:
        block = blocks.pop()  # with it goes the last hold on this block's input
        d_lp, d_x = _block_backward(*block, (d_x,) if "wf_g" in block[2] else (d_x, *of_aux))
        d_layers.append(d_lp)
    grads = {
        "embed": d_embed + _embed_backward(params["embed"], tokens, d_x, np.float32(1.0)), "g_f": d_gf,
        "head": d_head + weight * d_head_mtp, "g_e": d_ge, "g_h": d_gh, "g_s": weight * d_gs, "w_eh": d_weh,
        "layers": d_layers[::-1], "bias": jnp.zeros_like(params["bias"]),
    }
    return loss, parts, grads


def _free_choice(c, tokens):
    """``forced`` with every entry left free."""
    return np.full((len(expert_layers(c)), tokens.shape[0] * tokens.shape[1], c["num_experts_per_tok"]), -1, np.int32)


def train_step(params, state, tokens, c, o, products="float32"):
    """One optimizer step and one move of the biases; ``params`` and ``state``
    are consumed, and ``state`` comes back on the host (``adamw_init``)."""
    with jax.default_matmul_precision("highest"):
        loss, parts, grads = _gradients(params, tokens, c, o["coef"], products, _free_choice(c, tokens))
        params, state = _apply(params, grads, state, parts["expert_counts"], _freeze(o), c["bias_rate"])
    del parts["probs"], parts["last_logits"], parts["mtp_logits"]  # not what a step is read for
    return params, _to_host(state), loss, parts


def evaluate(params, tokens, c, coef, last, products="float32", forced=None):
    """Loss, its parts (``ce_mtp`` among them), the gradient's norm per
    parameter group and the logits ``(2, B, last, V)``: the trunk's of the last
    ``last`` positions and the module's of its last ``last``, at ``params``."""
    if last > min(LAST_LOGITS, tokens.shape[1] - 1):
        raise ValueError(f"the program gives the last {LAST_LOGITS} positions' logits, not {last}")
    with jax.default_matmul_precision("highest"):
        loss, parts, grads = _gradients(
            params, tokens, c, coef, products, _free_choice(c, tokens) if forced is None else forced
        )
        norms = _group_norms(grads)
    for leaf in jax.tree.leaves(grads):
        leaf.delete()
    return loss, parts, norms, jnp.stack([parts.pop("last_logits")[:, -last:], parts.pop("mtp_logits")[:, -last:]])


def group_norms(grads) -> dict:
    """L2 norm of the gradient over each parameter group of ``GROUPS`` (the
    biases have none)."""
    sq = dict.fromkeys(GROUPS, 0.0)
    for name in ("embed", "g_f", "head") + MODULE:
        sq[group_of(name)] = sq[group_of(name)] + jnp.sum(grads[name] ** 2)
    for lp in grads["layers"]:
        for name, g in lp.items():
            sq[group_of(name)] = sq[group_of(name)] + jnp.sum(g**2)
    return {k: jnp.sqrt(v) for k, v in sq.items()}


_group_norms = jax.jit(group_norms)
