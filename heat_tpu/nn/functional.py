"""Functional nn ops: jax.nn passthrough + distributed attention entry point.

Reference parity: ``heat.nn.functional`` forwards to ``torch.nn.functional``
(reference heat/nn/functional.py). Here unknown names resolve to ``jax.nn``
(relu, gelu, softmax, one_hot, …); the module's own surface is the
long-context attention front-end over :mod:`heat_tpu.parallel`.
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core.dndarray import DNDarray
from ..parallel import local_attention, ring_attention, ulysses_attention

__all__ = ["blocked_cross_entropy", "dense", "scaled_dot_product_attention"]


def dense(x, w, bias=None, activation=None):
    """Affine layer ``activation(x @ w + bias)`` on DNDarrays — the DP
    forward building block, expressed entirely in framework ops so the
    Fusion 2.0 engine compiles it as ONE cached program: the matmul is a
    lazy kernel node (core/fusion.py ``defer_matmul``) and the bias add +
    activation graft onto it as the kernel's epilogue. With
    ``HEAT_TPU_FUSION_REDUCE=0`` the same expression dispatches op by op,
    bit for bit.

    ``activation`` is ``None``, one of ``"relu"`` / ``"tanh"`` /
    ``"sigmoid"`` (compositions of fusable framework ops), or any callable
    taking and returning a DNDarray (a callable built from non-framework
    ops will flush the kernel first — still correct, just not one
    program)."""
    from ..core import arithmetics, exponential, statistics, trigonometrics
    from ..core.linalg import matmul

    y = matmul(x, w)
    if bias is not None:
        y = arithmetics.add(y, bias)
    if activation is None:
        return y
    if callable(activation):
        return activation(y)
    if activation == "relu":
        return statistics.maximum(y, 0.0)
    if activation == "tanh":
        return trigonometrics.tanh(y)
    if activation == "sigmoid":
        # 1 / (1 + exp(-y)) as fusable framework ops
        return arithmetics.div(
            1.0, arithmetics.add(exponential.exp(arithmetics.mul(y, -1.0)), 1.0)
        )
    raise ValueError(
        f"activation must be None, 'relu', 'tanh', 'sigmoid' or a callable, "
        f"got {activation!r}"
    )


def scaled_dot_product_attention(
    q: Union[jax.Array, DNDarray],
    k: Union[jax.Array, DNDarray],
    v: Union[jax.Array, DNDarray],
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    strategy: str = "auto",
    comm=None,
) -> Union[jax.Array, DNDarray]:
    """softmax(QKᵀ/√d)V with ``(batch, seq, heads, head_dim)`` layout.

    Dispatch: DNDarrays split along the sequence axis (axis 1) run the
    distributed kernels — ``strategy`` picks ``"ring"`` (K/V circulated over
    ICI, any head count) or ``"ulysses"`` (all_to_all head↔seq swap, needs
    heads % mesh size == 0); ``"auto"`` prefers ulysses when it applies since
    it does fewer hops. Everything else (replicated DNDarrays, raw arrays)
    runs the single-device blockwise kernel.
    """
    if strategy not in ("auto", "ring", "ulysses"):
        raise ValueError(
            f"strategy must be 'auto', 'ring' or 'ulysses', got {strategy!r}"
        )
    is_dnd = isinstance(q, DNDarray)
    if is_dnd:
        if not (isinstance(k, DNDarray) and isinstance(v, DNDarray)):
            raise TypeError("q, k, v must all be DNDarray or all jax.Array")
        if not (q.split == k.split == v.split):
            raise ValueError(
                f"q/k/v splits must match, got {q.split}/{k.split}/{v.split}"
            )
        comm = q.comm
        if q.ndim != 4:
            raise ValueError(f"expected (B, T, H, D) inputs, got ndim={q.ndim}")
        if q.split == 1 and comm.size > 1:
            seq_len = q.shape[1]
            h = q.shape[2]
            if strategy == "auto":
                strategy = "ulysses" if h % comm.size == 0 else "ring"
            fn = {"ring": ring_attention, "ulysses": ulysses_attention}[strategy]
            out = fn(
                q._masked(0), k._masked(0), v._masked(0),
                comm=comm, causal=causal, scale=scale, seq_len=seq_len,
            )
            return DNDarray(
                out, q.shape, q.dtype, q.split, q.device, comm, True
            )
        if q.split not in (None, 1):
            raise NotImplementedError(
                f"attention over split={q.split} not supported; resplit to 1"
            )
        out = local_attention(
            q._replicated(), k._replicated(), v._replicated(), causal=causal, scale=scale
        )
        return DNDarray.from_logical(out, q.split, q.device, q.comm)

    return local_attention(q, k, v, causal=causal, scale=scale)


def _position_blocks(block, *arrays):
    """Each array's leading axis padded with zeros to a multiple of ``block``
    and split into ``(blocks, block, ...)``."""
    pad = -arrays[0].shape[0] % block
    return tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(-1, block, *a.shape[1:])
        for a in arrays
    )


def _operand(x, dtype):
    return x if dtype is None else x.astype(dtype)


def _ce_blocks(hidden, kernel, targets, weights, block, dtype, grads):
    """One loop over the blocks of positions. A block's logits are taken once
    and from them the log-sum-exp and the cross-entropy of each position;
    with ``grads`` also ``(softmax - onehot) * weights`` and its two products,
    the hidden states' gradient block by block and the kernel's summed over
    the blocks in a float32 carry. Returns the weighted sum, the
    cross-entropy a position and the two gradients in float32 (without
    ``grads``: None)."""
    n, d = hidden.shape
    w = _operand(kernel, dtype)

    def one_block(dw, args):
        h, y, wt = args
        h = _operand(h, dtype)
        logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ce = lse - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        if not grads:
            return None, (ce, None)
        onehot = y[:, None] == jnp.arange(w.shape[1], dtype=y.dtype)[None, :]
        dlogits = _operand((jnp.exp(logits - lse[:, None]) - onehot) * wt[:, None], dtype)
        dh = jnp.dot(dlogits, w.T, preferred_element_type=jnp.float32)
        return dw + jnp.dot(h.T, dlogits, preferred_element_type=jnp.float32), (ce, dh)

    dw, (ce, dh) = jax.lax.scan(
        one_block,
        jnp.zeros(kernel.shape, jnp.float32) if grads else None,
        _position_blocks(block, hidden, targets, weights),
    )
    ce = ce.reshape(-1)[:n]
    return jnp.sum(ce * weights), ce, dh.reshape(-1, d)[:n] if grads else None, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _blocked_ce(hidden, kernel, targets, weights, block, dtype):
    return _ce_blocks(hidden, kernel, targets, weights, block, dtype, grads=False)[0]


def _blocked_ce_fwd(hidden, kernel, targets, weights, block, dtype):
    loss, ce, dh, dw = _ce_blocks(hidden, kernel, targets, weights, block, dtype, grads=True)
    return loss, (dh.astype(hidden.dtype), dw.astype(kernel.dtype), ce)


def _blocked_ce_bwd(block, dtype, res, g):
    """The loss is linear in its cotangent and the loop has formed the
    gradients at 1: what is left is to scale them (under ``value_and_grad``
    by the constant 1, which folds away)."""
    dh, dw, ce = res
    return (dh * g).astype(dh.dtype), (dw * g).astype(dw.dtype), None, ce * g


_blocked_ce.defvjp(_blocked_ce_fwd, _blocked_ce_bwd)


CE_BLOCK = 2048  # positions a block: 2048 x 50,304 float32 logits are 0.4 GB


def blocked_cross_entropy(hidden, kernel, targets, weights=None, *, dtype=None):
    """``sum(weights * ce)``, a float32 scalar, where ``ce`` is the
    cross-entropy of ``hidden @ kernel`` against integer ``targets`` a
    position and ``weights`` (None: ones) carries the mask and the mean's
    divisor, without ever holding the ``(positions, vocab)`` logits: the
    positions go through one loop in blocks of ``CE_BLOCK`` (the last one
    padded), and each block's logits are taken once, reduced and dropped.
    Where the call is differentiated, the same pass forms ``softmax - onehot``
    from those logits and both gradients with it, three products a block and
    no second loop; the backward pass only scales them by the cotangent. A
    call that is not differentiated forms no gradient. ``hidden`` is
    ``(N, D)``, ``kernel`` ``(D, V)``, ``targets`` and ``weights`` ``(N,)``;
    the products take ``dtype`` operands (None: as they are) and accumulate in
    float32, as do the log-sum-exp, the loss and the kernel's gradient over
    the blocks; both gradients come back in their argument's dtype, and the
    cotangent of ``weights`` is the cross-entropy a position."""
    n = hidden.shape[0]
    weights = jnp.ones((n,), jnp.float32) if weights is None else weights.astype(jnp.float32)
    return _blocked_ce(hidden, kernel, targets, weights, min(CE_BLOCK, n), dtype)


def __getattr__(name):
    """jax.nn passthrough (reference functional.py func_getattr analog)."""
    try:
        return getattr(jax.nn, name)
    except AttributeError:
        raise AttributeError(
            f"function {name} not implemented in jax.nn or heat_tpu.nn.functional"
        ) from None
