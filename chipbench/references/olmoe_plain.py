"""Plain reference for the ``lm_step`` kind: OLMoE (allenai, arXiv:2409.02060;
HF ``modeling_olmoe.py``) forward, loss, gradients and AdamW in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``. It imports
nothing of heat_tpu. It also makes what a run is made from: the initial
weights and the token batches, from the seed.

    x  = Embed[tokens]                                  no position table
    per layer:
      h  = RMSNorm(x; g_in)
      q  = RMSNorm(h Wq; g_q)  k = RMSNorm(h Wk; g_k)  v = h Wv     norms over all of hidden
      q, k -> heads, rotary (rotate-half), causal softmax(q k^T / sqrt(d_head)) v
      x  = x + concat(heads) Wo
      h  = RMSNorm(x; g_post)
      r  = h Wr;  p = softmax(r);  (w_j, e_j) = top-k of p, not renormalised
      x  = x + sum_j w_j (silu(h Wg[e_j]) * (h Wu[e_j])) Wd[e_j]
    logits = RMSNorm(x; g_f) W_head
    loss = CE(next token) + c_lb * E * sum_e f_e P_e + c_z * mean(logsumexp(r)^2)

Departures from the published code, none of which changes a number at one
layer: (1) ``f_e`` is the share of the N*k assignments that went to expert
``e`` (HF divides the per-slot counts so that its sum is larger by the factor
k; the configuration's ``assumed`` states the form); with several layers each
layer's two terms are taken by themselves and averaged, where HF pools the
layers' tokens first; (2) the experts are a loop over all experts on all
tokens with a zero weight where an expert was not chosen (a ``scan``, each
turn recomputed in the backward pass), attention is taken one head at a
time and the cross-entropy one sequence at a time: all three only so that the reference fits beside its own
optimizer state on the chip; (3) padding, attention masks other than the
causal one, the KV cache and dropout do not exist here.

``products="bf16"`` is the **control**, the reference computed a precision
below the one the configuration states: every matrix product takes bfloat16
operands *and accumulates in bfloat16* (the sum rounded every 128 terms, as a
kernel with a bfloat16 accumulator between its passes over the contraction
would), and the norms, the router's softmax and the top-k weights are
bfloat16 too. ``correct`` must refuse it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GROUPS = ("embed", "attention", "norms", "router", "experts", "head")


# -- what a run is made from ------------------------------------------------------


def param_shapes(c: dict) -> dict:
    d, e, f, v = c["hidden_size"], c["num_experts"], c["intermediate_size"], c["vocab_size"]
    layer = {
        "g_in": (d,), "wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
        "g_q": (d,), "g_k": (d,), "g_post": (d,), "wr": (d, e),
        "wg": (e, d, f), "wu": (e, d, f), "wd": (e, f, d),
    }
    return {
        "embed": (v, d), "g_f": (d,), "head": (d, v),
        "layers": [dict(layer) for _ in range(c["num_hidden_layers"])],
    }


def group_of(name: str) -> str:
    if name.startswith("g_"):
        return "norms"
    return {"embed": "embed", "head": "head", "wr": "router",
            "wg": "experts", "wu": "experts", "wd": "experts"}.get(name, "attention")


def init_params(seed: int, c: dict, std: float = 0.02) -> dict:
    """normal(0, std) matrices and unit norm gains, float32, made on the
    device: leaf ``i`` (in the order of ``param_shapes``) from
    ``fold_in(PRNGKey(seed mod 2^31), i)``."""
    shapes = param_shapes(c)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.PRNGKey(seed % (2**31))
    out = []
    for i, shape in enumerate(leaves):
        if len(shape) == 1:
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(_normal(jax.random.fold_in(key, i), shape, std))
    return jax.tree.unflatten(tree, out)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def zipf_cdf(vocab: int, s: float = 1.0) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** s
    return np.cumsum(p / p.sum())


def batch(seed: int, i: int, sequences: int, length: int, cdf: np.ndarray) -> np.ndarray:
    """Batch ``i`` of a run: token ids drawn independently with
    P(id = r) proportional to 1/(r+1)^s (``cdf`` from ``zipf_cdf``), on the
    host, int32, no document boundary inside a sequence."""
    rng = np.random.default_rng([seed, i + 1])
    ids = np.searchsorted(cdf, rng.random((sequences, length)), side="right")
    return np.minimum(ids, len(cdf) - 1).astype(np.int32)


# -- the model --------------------------------------------------------------------


class _Numerics:
    def __init__(self, products: str):
        if products not in ("float32", "bf16"):
            raise ValueError(products)
        self.low = products == "bf16"
        self.soft = jnp.bfloat16 if self.low else jnp.float32

    def mm(self, a, b):
        if not self.low:
            return jnp.matmul(a, b)
        # a bfloat16 accumulator: rounded after every `step` terms of the sum
        # (128, the matrix unit's depth; 16 where the sum is shorter than 256)
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        k = a.shape[-1]
        step = 128 if k >= 256 else 16
        acc = jnp.zeros(a.shape[:-1] + b.shape[-1:], jnp.bfloat16)
        for i in range(0, k, step):
            part = jnp.matmul(a[..., i:i + step], b[i:i + step], preferred_element_type=jnp.float32)
            acc = (acc.astype(jnp.float32) + part).astype(jnp.bfloat16)
        return acc.astype(jnp.float32)

    def rms(self, x, g, eps):
        x = x.astype(self.soft)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + jnp.asarray(eps, self.soft))
        return (y * g.astype(self.soft)).astype(jnp.float32)


def _rotary(x, theta):
    """x: (B, T, H, D), rotate-half, angles t * theta^(-2i/D)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(num, c, lp, h):
    b, t, d = h.shape
    heads = c["num_attention_heads"]
    eps = c["rms_norm_eps"]
    q = num.rms(num.mm(h, lp["wq"]), lp["g_q"], eps)
    k = num.rms(num.mm(h, lp["wk"]), lp["g_k"], eps)
    v = num.mm(h, lp["wv"])
    split = lambda a: a.reshape(b, t, heads, d // heads)  # noqa: E731
    q, k, v = _rotary(split(q), c["rope_theta"]), _rotary(split(k), c["rope_theta"]), split(v)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(qkv):  # (T, d_head) each; one head at a time bounds the memory
        qh, kh, vh = qkv
        s = jnp.where(causal, num.mm(qh, kh.T) / np.sqrt(d // heads), -jnp.inf)
        return num.mm(jax.nn.softmax(s.astype(num.soft), axis=-1).astype(jnp.float32), vh)

    by_head = lambda a: a.transpose(0, 2, 1, 3).reshape(b * heads, t, d // heads)  # noqa: E731
    o = jax.lax.map(one_head, (by_head(q), by_head(k), by_head(v)))
    o = o.reshape(b, heads, t, d // heads).transpose(0, 2, 1, 3).reshape(b, t, d)
    return num.mm(o, lp["wo"])


def route(num, c, lp, h, forced=None):
    """Router logits (float32), probabilities, the top-k weights and experts
    for tokens ``h (N, D)``. With ``forced (N, k)`` those experts are taken
    in place of the top-k, each at its own probability here."""
    r = num.mm(h, lp["wr"])
    p = jax.nn.softmax(r.astype(num.soft), axis=-1)
    if forced is None:
        w, e = jax.lax.top_k(p, c["num_experts_per_tok"])
    else:
        w, e = jnp.take_along_axis(p, forced, axis=-1), forced
    return r, p.astype(jnp.float32), w.astype(jnp.float32), e


def _experts(num, c, lp, h, forced=None):
    """h: (N, D). A loop over all experts; an expert that did not choose a
    token weighs it with 0. Returns the layer's output, its two auxiliary
    terms and the count of assignments per expert."""
    n, n_exp = h.shape[0], c["num_experts"]
    r, p, w, e = route(num, c, lp, h, forced)
    # (N, E): weight of expert e for token n, 0 where it was not chosen
    dense_w = jnp.zeros((n, n_exp), jnp.float32).at[jnp.arange(n)[:, None], e].add(w)

    @jax.checkpoint
    def one(acc, ex):
        wg, wu, wd, w_e = ex
        y = num.mm(jax.nn.silu(num.mm(h, wg)) * num.mm(h, wu), wd)
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (lp["wg"], lp["wu"], lp["wd"], dense_w.T))
    counts = jnp.zeros((n_exp,), jnp.int32).at[e.reshape(-1)].add(1)
    f = counts.astype(jnp.float32) / (n * c["num_experts_per_tok"])
    load_balance = n_exp * jnp.sum(f * p.mean(axis=0))
    router_z = jnp.mean(jax.nn.logsumexp(r, axis=-1) ** 2)
    return out, load_balance, router_z, counts, e, p


def hidden_states(params, tokens, c, products="float32", forced=None):
    """Final-norm output (B, T, D), and per layer the auxiliary terms, the
    counts, the chosen experts (N, k) and the router's probabilities (N, E).
    ``forced (layers, N, k)`` fixes every layer's experts (``route``): two
    computations then differ by their arithmetic alone, not by which of two
    nearly tied experts each happened to choose."""
    num = _Numerics(products)
    eps = c["rms_norm_eps"]
    x = params["embed"][tokens]
    b, t, d = x.shape
    aux = []
    for i, lp in enumerate(params["layers"]):
        x = x + _attention(num, c, lp, num.rms(x, lp["g_in"], eps))
        y, *rest = _experts(
            num, c, lp, num.rms(x, lp["g_post"], eps).reshape(b * t, d),
            None if forced is None else forced[i],
        )
        x = x + y.reshape(b, t, d)
        aux.append(rest)
    return num.rms(x, params["g_f"], eps), aux


def logits_of(params, tokens, c, products="float32", last: int = 0, forced=None):
    """Logits of the last ``last`` positions (all where 0) of each sequence."""
    num = _Numerics(products)
    h, aux = hidden_states(params, tokens, c, products, forced)
    return num.mm(h[:, -last:], params["head"]), aux


def loss_parts(params, tokens, c, coef, products="float32", forced=None):
    """``(loss, parts)``: parts = ce, load_balance, router_z (means over the
    layers), expert_counts (layers x experts), chosen (layers x N x k), probs
    (layers x N x experts)."""
    num = _Numerics(products)
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
    }


# -- AdamW with a clip at the global norm ------------------------------------------


def adamw_init(params):
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    return {"m": zeros(), "v": zeros(), "count": jnp.zeros((), jnp.int32)}


def adamw_update(params, grads, state, o, low_moments=False):
    """Clip the gradient at global norm ``o['clip']``, then AdamW (decoupled
    weight decay on every parameter, bias-corrected moments) at the learning
    rate ``lr * min(1, step / warmup_steps)``, steps counted from 1.
    ``low_moments`` is the control: both moments rounded to bfloat16
    (``reduce_precision``: a pair of casts is what a compiler that allows
    excess precision, as the TPU's does, may drop)."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip"] / jnp.maximum(norm, 1e-30))
    count = state["count"] + 1
    c1 = 1.0 - o["b1"] ** count.astype(jnp.float32)
    c2 = 1.0 - o["b2"] ** count.astype(jnp.float32)
    lr = o["lr"] * jnp.minimum(1.0, count.astype(jnp.float32) / max(o["warmup_steps"], 1))

    def stored(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) if low_moments else x

    def leaf(p, g, m, v):
        g = g * scale
        m = stored(o["b1"] * m + (1.0 - o["b1"]) * g)
        v = stored(o["b2"] * v + (1.0 - o["b2"]) * g * g)
        step = (m / c1) / (jnp.sqrt(v / c2) + o["eps"]) + o["weight_decay"] * p
        return p - lr * step, m, v

    out = jax.tree.map(leaf, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))  # noqa: E731
    return pick(0), {"m": pick(1), "v": pick(2), "count": count}


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 2))
def _update_gaps(params, grads, state, got, o_items):
    o = _thaw(o_items)
    if got is None:
        got, _ = adamw_update(params, grads, state, o, low_moments=True)
    new, state = adamw_update(params, grads, state, o)
    norm = lambda a: jnp.sqrt(jnp.sum(a * a))  # noqa: E731
    gaps = jax.tree.map(
        lambda n, p, s: norm(s - n) / jnp.maximum(norm(n - p), 1e-30), new, params, got
    )
    return new, state, gaps


def update_gaps(params, grads, state, got, o):
    """One step of this file's AdamW on gradients it is given, beside the
    parameters ``got`` that another optimizer made of the same ``params`` and
    ``grads``: per leaf, the norm of ``got - new`` over the norm of the
    update ``new - params``. With the gradients shared the gap is what the
    two optimizers do differently (learning rate, moments, bias correction,
    decay, clip), not what their gradients' rounding does to ``m / sqrt(v)``.
    ``got=None`` is the control: this AdamW with both moments in bfloat16.
    ``params`` and ``state`` are consumed; returns ``(new, state, gaps)``."""
    return _update_gaps(params, grads, state, got, _freeze(o))


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(0, 1))
def _train_step(params, state, tokens, c_items, o_items, products):
    c, o = _thaw(c_items), _thaw(o_items)
    (loss, parts), grads = jax.value_and_grad(loss_parts, has_aux=True)(
        params, tokens, c, o["coef"], products
    )
    params, state = adamw_update(params, grads, state, o)
    return params, state, loss, parts


def train_step(params, state, tokens, c, o, products="float32"):
    """One optimizer step; ``params`` and ``state`` are consumed."""
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
    logits of the last ``last`` positions, at ``params``; ``forced`` as in
    ``hidden_states``."""
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


def _freeze(d):
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v) for k, v in d.items()))


def _thaw(items):
    return {k: _thaw(v) if isinstance(v, tuple) else v for k, v in items}


# -- the numbers of ``correct`` -----------------------------------------------------


def rel_gap(got, want) -> float:
    """Largest |got - want| over the largest |want|."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-30))


def rms_gap(got, want) -> float:
    """Root mean square of got - want over that of want."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / max(float(np.sqrt(np.mean(want**2))), 1e-30))


def routing_disagreement(chosen_got, probs_want, k: int, slack: float) -> float:
    """Share of the assignments in ``chosen_got (N, k)`` that the reference
    could not have made: an expert whose reference probability lies more
    than ``slack`` (relative) under the reference's k-th largest for that
    token. A choice between two candidates closer than the rounding of the
    router's input is no disagreement."""
    probs_want = np.asarray(probs_want, np.float64)
    kth = np.sort(probs_want, axis=-1)[:, -k][:, None]
    p_got = np.take_along_axis(probs_want, np.asarray(chosen_got), axis=-1)
    return float(np.mean(p_got < kth * (1.0 - slack)))
