"""Plain reference for the ``ouro_step`` kind: Ouro-2.6B (ByteDance;
``config.json`` of ``ByteDance/Ouro-2.6B``, ``model_type`` ``ouro``; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741; the layers of
the release's ``modeling_ouro.py`` as the configuration's ``assumed`` describes
them) forward, the stage-I loss, gradients and AdamW in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``. It imports nothing of
heat_tpu; AdamW, the batches and the numbers of ``correct`` are
``olmoe_plain.py``'s, the masked attention, rotary and the bfloat16 control's
arithmetic ``trinity_plain.py``'s. ``L`` blocks, ``P = total_ut_steps`` passes:

    N(x; g)     = x rsqrt(mean x^2 + eps) g                       plain RMSNorm, g starts at 1
    h(0)        = Embed[tokens]
    block_l(u):   a = u + N(Attn_l(N(u; g_1)); g_2);   out = a + N(SwiGLU_l(N(a; g_3)); g_4)      four norms a block
      Attn:   q, k, v = h Wq, h Wk, h Wv -> heads of head_dim (as many key-value heads as query heads), no bias, no q/k
              norm; rotate-half rotary (theta) on q and k; softmax(q k^T / sqrt(head_dim)) v, t sees every j <= t; heads Wo
      SwiGLU: (silu(h Wf_g) * (h Wf_u)) Wf_d, no bias
    h(t)        = N(block_{L-1}(... block_0(h(t-1)) ...); g_f)    t = 1..P: the same L blocks and the same g_f every pass
    z_t         = h(t) W_head                                     one head, P uses
    lam_t       = sigmoid(h(t) . w_gate + b_gate)                 t = 1..P-1: one gate, P - 1 uses
    p_1 = lam_1;  p_t = lam_t prod_{j<t} (1 - lam_j);  p_P = prod_{j<P} (1 - lam_j)
    loss        = mean_i [ sum_t p_t(i) CE(z_t(i), x_{i+1}) - beta H(p(i)) ],   H(p) = -sum_t p_t log p_t

over the ``T - 1`` positions of a sequence that have a next token.

Departures from the published description: (1) **the cut**: ``L`` is the
configuration's ``num_hidden_layers`` (8 of the published 48: a pipeline
stage's blocks), so pass ``t + 1``'s block 0 reads ``g_f``'s norm of pass ``t``'s
block ``L - 1``: a whole ``L``-block looped model, here and in the program alike;
(2) the loss is the paper's stage I alone (its second stage, the gate trained by
itself on the loss's improvement a pass, is left out); ``beta`` is the
configuration's; (3) only so that it fits beside its optimizer state and
compiles in seconds: a block a program, used ``P x L`` times forward and as often
backward (each block computed again from its kept input, a Python loop over
passes and blocks, no scan), attention a head and a block of queries at a time,
the cross-entropy in blocks of positions; (4) no cache, no dropout, no document
boundaries, no early exit (training reads no ``early_exit_threshold``).

``products="operands"`` is the precision the configuration **states**, no more
and no less: every matrix product takes its operands rounded to bfloat16 and
accumulates in float32, everything else is float32 (:func:`last_exits` computes
the exits so: a program that keeps the stated precision rounds the same values
at the same places and lies far nearer to it than to the float32 reference,
which is what tells it from the control). ``products="bf16"`` is the **control**
a precision below the configuration's
(``trinity_plain._Numerics``: bfloat16 operands, accumulators, norms and
softmax; here also the stream between the blocks, the gate and the exit
distribution). Further controls are keys of ``c`` that the configuration does
not have, each a way to get the looped model wrong: ``passes_run`` (fewer passes
than ``total_ut_steps``: the exits that were not run repeat the last one that
was, with no probability), ``ln_f_once`` (the final norm outside the loop: an
exit reads it, the next pass does not), ``last_exit_only`` (``p = (0, .., 0,
1)``), ``stop_gate`` (the exit distribution a constant under differentiation),
``one_use`` (a shared weight's gradient taken from the last pass alone) and the
changed coefficient ``beta``. ``correct`` must refuse each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.olmoe_plain import (  # noqa: F401  (the kind reads these from here)
    _freeze, _normal, _thaw, adamw_init, adamw_update, batch, rel_gap, rms_gap, update_gaps, zipf_cdf,
)
from chipbench.references.trinity_plain import LAST_LOGITS, TOKEN_BLOCK, masked_attention, rotary
from chipbench.references.trinity_plain import _Numerics as _TwoPrecisions

GROUPS = ("embed", "attention", "norms", "dense", "gate", "head")
WRITES_TO_STREAM = ("wo", "wf_d")
ATTENTION = ("wq", "wk", "wv", "wo")


# -- what a run is made from ------------------------------------------------------


def param_shapes(c: dict) -> dict:
    d, v, f = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    q, kv = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    layer = {
        "g_1": (d,), "g_2": (d,), "g_3": (d,), "g_4": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
        "wf_g": (d, f), "wf_u": (d, f), "wf_d": (f, d),
    }
    return {
        "embed": (v, d), "g_f": (d,), "head": (d, v), "w_gate": (d,), "b_gate": (),
        "layers": [dict(layer) for _ in range(c["num_hidden_layers"])],
    }


def group_of(name: str) -> str:
    if name.startswith("g_"):
        return "norms"
    if name in ("embed", "head"):
        return name
    if name.endswith("_gate"):
        return "gate"
    return "attention" if name in ATTENTION else "dense"


def init_params(seed: int, c: dict, std: float = 0.02, out_std=None, gate_std=None) -> dict:
    """Float32, made on the device, leaf ``i`` (in the order of
    ``param_shapes``) from ``fold_in(PRNGKey(seed mod 2^31), i)``: matrices
    normal(0, std), those that write into the residual stream (``wo``,
    ``wf_d``) normal(0, out_std), the gate's weights normal(0, gate_std) and
    its bias 0, norm gains 1."""
    of = {"w_gate": std if gate_std is None else gate_std, **dict.fromkeys(WRITES_TO_STREAM, std if out_std is None else out_std)}
    paths, tree = jax.tree_util.tree_flatten_with_path(param_shapes(c), is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.PRNGKey(seed % (2**31))
    out = []
    for i, (path, shape) in enumerate(paths):
        name = path[-1].key
        if name.startswith("g_"):
            out.append(jnp.ones(shape, jnp.float32))
        elif name == "b_gate":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            out.append(_normal(jax.random.fold_in(key, i), shape, of.get(name, std)))
    return jax.tree.unflatten(tree, out)


# -- the model --------------------------------------------------------------------


class _Numerics(_TwoPrecisions):
    """``trinity_plain._Numerics`` (``float32``, ``bf16``) and between them
    ``operands``: float32 but for the operands of a matrix product, rounded to
    bfloat16 as the configuration's guarantee states."""

    def __init__(self, products: str):
        self.operands = products == "operands"
        super().__init__("float32" if self.operands else products)

    def mm(self, a, b):
        if self.operands:
            return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        return super().mm(a, b)


def _stream(num, x):
    """The residual stream between two blocks: float32, or the control's bfloat16."""
    return x.astype(num.soft).astype(jnp.float32)


def block(num, c, lp, u):
    """One block on ``u (B, T, D)``, as the module docstring writes it."""
    b, t, _ = u.shape
    eps, dh, theta = c["rms_norm_eps"], c["head_dim"], c["rope_theta"]
    h = num.rms(u, lp["g_1"], eps)
    heads = lambda a: a.reshape(b, t, -1, dh)  # noqa: E731
    q, k, v = rotary(heads(num.mm(h, lp["wq"])), theta), rotary(heads(num.mm(h, lp["wk"])), theta), heads(num.mm(h, lp["wv"]))
    mixed = num.mm(masked_attention(num, q, k, v, None).reshape(b, t, -1), lp["wo"])
    a = _stream(num, u + num.rms(mixed, lp["g_2"], eps))
    h = num.rms(a, lp["g_3"], eps)
    y = num.mm(jax.nn.silu(num.mm(h, lp["wf_g"])) * num.mm(h, lp["wf_u"]), lp["wf_d"])
    return _stream(num, a + num.rms(y, lp["g_4"], eps))


def passes_of(c: dict) -> int:
    return c.get("passes_run", c["total_ut_steps"])


def exit_pdf(num, c, exits, w_gate, b_gate):
    """``p (P, B, T)`` from the exits' hidden states ``(P, B, T, D)``: the gate
    after every pass but the last, the products written out."""
    total, run = c["total_ut_steps"], passes_of(c)
    if c.get("last_exit_only", False):  # the control: no gate at all
        return jnp.zeros(exits.shape[:3], jnp.float32).at[-1].set(1.0)
    lam = jax.nn.sigmoid((jnp.sum(exits[:run - 1].astype(num.soft) * w_gate.astype(num.soft), axis=-1) + b_gate.astype(num.soft)))
    p, left = [], jnp.ones(exits.shape[1:3], num.soft)
    for t in range(run - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    p.append(left)
    p += [jnp.zeros_like(left)] * (total - run)  # the control: the passes that were not run take no probability
    p = jnp.stack(p).astype(jnp.float32)
    return jax.lax.stop_gradient(p) if c.get("stop_gate", False) else p


def _entropy_terms(p):
    return -jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)


def _exits_loss(num, c, beta, head, w_gate, b_gate, exits, tokens):
    """The loss from the exits' hidden states, and its parts: the
    cross-entropies ``TOKEN_BLOCK`` positions' logits at a time."""
    passes, b, t, d = exits.shape
    p = exit_pdf(num, c, exits, w_gate, b_gate)
    block_ = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    split = lambda a: a.reshape((a.shape[0] * (t // block_), block_) + a.shape[2:])  # noqa: E731
    targets = jnp.roll(tokens, -1, axis=1)
    counted = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t))  # the last position has no next token

    @jax.checkpoint
    def one_block(args):  # a block of positions' logits only, and again in the backward pass
        hs, ys = args
        logits = num.mm(hs, head)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, ys[:, None], axis=-1)[:, 0]

    ce = jnp.stack([
        jax.lax.map(one_block, (split(exits[i]), split(targets))).reshape(b, t) for i in range(passes)
    ])  # (P, B, T): the cross-entropy of every exit at every position
    n = b * (t - 1)
    expected = jnp.sum(jnp.where(counted, p * ce, 0.0)) / n
    entropy = jnp.sum(jnp.where(counted, _entropy_terms(p), 0.0)) / n
    steps = jnp.arange(1, passes + 1, dtype=jnp.float32)[:, None, None]
    parts = {
        "ce": expected, "exit_entropy": entropy, "expected_pass": jnp.sum(jnp.where(counted, p * steps, 0.0)) / n,
        "ce_by_exit": jnp.sum(jnp.where(counted, ce, 0.0), axis=(1, 2)) / n, "pdf": jax.lax.stop_gradient(p),
    }
    return expected - beta * entropy, parts


def hidden_states(params, tokens, c, products="float32"):
    """The exits' hidden states ``(P, B, T, D)``: a Python loop over passes and
    blocks. (The controls: ``passes_run`` stops early and repeats its last
    exit; ``ln_f_once`` gives the next pass the stream before the norm.)"""
    num = _Numerics(products)
    eps = c["rms_norm_eps"]
    x = _stream(num, params["embed"][tokens])
    exits = []
    for _ in range(passes_of(c)):
        for lp in params["layers"]:
            x = block(num, c, lp, x)
        h = _stream(num, num.rms(x, params["g_f"], eps))
        exits.append(h)
        if not c.get("ln_f_once", False):
            x = h
    exits += [exits[-1]] * (c["total_ut_steps"] - len(exits))
    return jnp.stack(exits)


def logits_of(params, tokens, c, products="float32", last: int = 0):
    """Every exit's logits ``(P, B, last, V)`` of the last ``last`` positions
    (all where 0)."""
    return _Numerics(products).mm(hidden_states(params, tokens, c, products)[:, :, -last:], params["head"])


def loss_parts(params, tokens, c, coef, products="float32"):
    """``(loss, parts)``: parts = ce (the expectation), exit_entropy,
    expected_pass, ce_by_exit ``(P,)`` and pdf ``(P, B, T)``. Autodiff of this
    is what :func:`_gradients` writes out (``tests/test_ouro.py`` holds the two
    together)."""
    num = _Numerics(products)
    exits = hidden_states(params, tokens, c, products)
    return _exits_loss(num, c, coef["beta"], params["head"], params["w_gate"], params["b_gate"], exits, jnp.asarray(tokens))


# -- steps and evaluations ----------------------------------------------------------


_NOT_THE_BLOCK = ("num_hidden_layers", "vocab_size", "total_ut_steps", "passes_run", "ln_f_once", "last_exit_only",
                  "stop_gate", "one_use")  # what a block's program does not read


def _block_key(c):
    return _freeze({k: v for k, v in c.items() if k not in _NOT_THE_BLOCK})


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block_forward(key, products, lp, x):
    return block(_Numerics(products), _thaw(key), lp, x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block_backward(key, products, lp, x, d_out):
    """The block again from its input, and its output's cotangent pulled back
    to its parameters and its input."""
    return jax.vjp(lambda lp, x: block(_Numerics(products), _thaw(key), lp, x), lp, x)[1](d_out)


def _final_norm(products, eps, g_f, x):
    num = _Numerics(products)
    return _stream(num, num.rms(x, g_f, eps))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _norm_forward(products, eps, g_f, x):
    return _final_norm(products, eps, g_f, x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _norm_backward(products, eps, g_f, x, d_out):
    return jax.vjp(lambda g_f, x: _final_norm(products, eps, g_f, x), g_f, x)[1](d_out)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _exits_backward(key, products, last, beta, head, w_gate, b_gate, exits, tokens):
    """From the exits' hidden states: the loss, its parts, every exit's logits
    of the last ``last`` positions, and the loss's gradients by the head, the
    gate and the exits."""
    num = _Numerics(products)

    def f(head, w_gate, b_gate, exits):
        loss, parts = _exits_loss(num, _thaw(key), beta, head, w_gate, b_gate, exits, tokens)
        parts["last_logits"] = jax.lax.stop_gradient(num.mm(exits[:, :, -last:], head))
        return loss, parts

    return jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)(head, w_gate, b_gate, exits)


@jax.jit
def _embed_backward(embed, tokens, d_x):
    return jnp.zeros_like(embed).at[tokens].add(d_x)


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))


def _passes_forward(params, tokens, c, products, keep=True):
    """The passes over the blocks, a block a program: the exits' hidden states
    (a list of ``total_ut_steps``) and, where ``keep``, every application's
    input and every pass's stream before the final norm, which the backward
    pass written out below reads."""
    key, eps = _block_key(c), c["rms_norm_eps"]
    num = _Numerics(products)
    run = passes_of(c)
    x = _stream(num, params["embed"][tokens])
    inputs, before_norm, exits = [], [], []
    for _ in range(run):
        for lp in params["layers"]:
            if keep:
                inputs.append(x)
            x = _block_forward(key, products, lp, x)
        if keep:
            before_norm.append(x)
        h = _norm_forward(products, eps, params["g_f"], x)
        exits.append(h)
        if not c.get("ln_f_once", False):
            x = h
    exits += [exits[-1]] * (c["total_ut_steps"] - run)
    return inputs, before_norm, exits


@functools.partial(jax.jit, static_argnums=(0,))
def _head_forward(products, hidden, head):
    return _Numerics(products).mm(hidden, head)


def last_exits(params, tokens, c, last, products="operands"):
    """Every exit's hidden state ``(P, B, last, D)`` and logits ``(P, B, last,
    V)`` of the last ``last`` positions, forward only: at ``operands``, what a
    program of exactly the stated precision gives."""
    with jax.default_matmul_precision("highest"):
        hidden = jnp.stack([h[:, -last:] for h in _passes_forward(params, jnp.asarray(tokens), c, products, keep=False)[2]])
        return hidden, _head_forward(products, hidden, params["head"])


def _gradients(params, tokens, c, coef, products):
    """Loss, parts (with every exit's last ``LAST_LOGITS`` positions' logits)
    and every gradient: backpropagation written out over passes and blocks, **a
    block a program** (forward: each application's input is kept; backward,
    from the last pass's last block down: an application is computed again from
    its input and its cotangent pulled back). A shared weight's gradient is the
    sum over its ``P`` uses, the stream's cotangent at a pass's end the sum of
    that exit's (from the head and the gate) and the next pass's."""
    key, eps = _block_key(c), c["rms_norm_eps"]
    tokens = jnp.asarray(tokens)
    layers, run = params["layers"], passes_of(c)
    inputs, before_norm, exits = _passes_forward(params, tokens, c, products)
    x = exits[-1]
    last = min(LAST_LOGITS, tokens.shape[1])
    exits_key = _freeze({k: v for k, v in c.items() if k != "num_hidden_layers"})
    (loss, parts), (d_head, d_wg, d_bg, d_exits) = _exits_backward(
        exits_key, products, last, float(coef["beta"]), params["head"], params["w_gate"], params["b_gate"], jnp.stack(exits), tokens
    )
    del exits
    d_exits = [d_exits[i] for i in range(c["total_ut_steps"])]
    for i in range(run, c["total_ut_steps"]):  # the control's repeated exits are one array
        d_exits[run - 1] = d_exits[run - 1] + d_exits[i]
    d_layers, d_gf, d_x = None, jnp.zeros_like(params["g_f"]), jnp.zeros_like(x)
    for t in reversed(range(run)):
        if c.get("ln_f_once", False):  # the norm stood beside the stream: its cotangent joins the stream's
            g, d_n = _norm_backward(products, eps, params["g_f"], before_norm.pop(), d_exits[t])
            d_x = d_x + d_n
        else:
            g, d_x = _norm_backward(products, eps, params["g_f"], before_norm.pop(), d_exits[t] + d_x)
        d_gf = d_gf + g
        d_pass = []
        for lp in reversed(layers):
            d_lp, d_x = _block_backward(key, products, lp, inputs.pop(), d_x)
            d_pass.append(d_lp)
        d_pass = d_pass[::-1]
        if d_layers is None:
            d_layers = d_pass
        elif not c.get("one_use", False):  # the control keeps the last pass's alone
            d_layers = _add(d_layers, d_pass)
    grads = {
        "embed": _embed_backward(params["embed"], tokens, d_x), "g_f": d_gf, "head": d_head, "w_gate": d_wg,
        "b_gate": d_bg, "layers": d_layers,
    }
    return loss, parts, grads


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0, 1, 2))
def _apply(params, grads, state, o_items):
    return adamw_update(params, grads, state, _thaw(o_items))


def train_step(params, state, tokens, c, o, products="float32"):
    """One optimizer step; ``params`` and ``state`` are consumed."""
    with jax.default_matmul_precision("highest"):
        loss, parts, grads = _gradients(params, tokens, c, o["coef"], products)
        params, state = _apply(params, grads, state, _freeze(o))
    del parts["pdf"], parts["last_logits"]  # not what a step is read for
    return params, state, loss, parts


def evaluate(params, tokens, c, coef, last, products="float32"):
    """Loss, its parts (``pdf`` among them), the gradient's norm per parameter
    group and every exit's logits ``(P, B, last, V)`` of the last ``last``
    positions, at ``params``."""
    if last > LAST_LOGITS:
        raise ValueError(f"the program gives the last {LAST_LOGITS} positions' logits, not {last}")
    with jax.default_matmul_precision("highest"):
        loss, parts, grads = _gradients(params, tokens, c, coef, products)
        norms = _group_norms(grads)
    for leaf in jax.tree.leaves(grads):
        leaf.delete()
    return loss, parts, norms, parts.pop("last_logits")[:, :, -last:]


def group_norms(grads) -> dict:
    """L2 norm of the gradient over each parameter group of ``GROUPS``; a
    block's is of the gradient summed over its passes."""
    sq = dict.fromkeys(GROUPS, 0.0)
    for name in ("embed", "g_f", "head", "w_gate", "b_gate"):
        sq[group_of(name)] = sq[group_of(name)] + jnp.sum(grads[name] ** 2)
    for lp in grads["layers"]:
        for name, g in lp.items():
            sq[group_of(name)] = sq[group_of(name)] + jnp.sum(g**2)
    return {k: jnp.sqrt(v) for k, v in sq.items()}


_group_norms = jax.jit(group_norms)
