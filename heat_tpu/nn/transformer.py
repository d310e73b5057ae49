"""Transformer blocks over the device mesh — the flagship model family.

The reference framework has no transformer/attention code at all (SURVEY
§2.5: "no transformer code"); its parallelism mechanisms (ring schedule,
axis-aware Alltoall) are exactly what long-context attention is made of.
This module is the capability those mechanisms exist for, built TPU-first
as flax modules:

* :class:`TransformerBlock` — pre-LN block: attention (XLA online-softmax,
  the Pallas flash kernel, or a sequence-parallel schedule) + SwiGLU MLP.
* :class:`TransformerLM` — embedding → N blocks → final LN → logit
  projection; a complete causal LM forward.

Parallelism is selected by ``attn_impl``:

- ``"local"`` — single-shard XLA blockwise attention.
- ``"flash"`` — the hand-tiled Pallas kernel
  (:func:`heat_tpu.parallel.flash_attention`); with ``comm=`` it runs per
  batch shard (data parallel over the mesh).
- ``"ring"`` / ``"ulysses"`` — sequence-parallel over a mesh axis, for
  sequences sharded with :class:`heat_tpu.MeshCommunication` (pass
  ``comm=``). Ring keeps K/V moving over ICI; ulysses swaps sequence↔heads
  with two all_to_alls.

Weights are plain flax params — shard them with `jax.sharding` NamedSharding
(tp: column/row-split the Dense kernels; dp: replicate) exactly as any flax
model; the dryrun (`__graft_entry__.py`) exercises a dp×sp layout.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


def _attend(q, k, v, *, impl, causal, comm, block_size, flash_bwd_impl):
    from ..parallel import (
        flash_attention,
        local_attention,
        ring_attention,
        ulysses_attention,
    )

    if impl == "flash":
        # block_size None = the kernel's tuned tiles
        blocks = {} if block_size is None else {
            "block_q": block_size, "block_k": block_size,
        }
        attend = functools.partial(
            flash_attention, causal=causal, bwd_impl=flash_bwd_impl, **blocks
        )
        if comm is not None and comm.size > 1:
            # data parallel: the kernel runs on each chip's batch shard. A
            # bare pallas_call is opaque to the SPMD partitioner, which
            # would gather the sharded batch onto every chip to call it.
            spec = comm.spec(0, 4)
            return jax.shard_map(
                attend, mesh=comm.mesh, in_specs=(spec, spec, spec),
                out_specs=spec,
            )(q, k, v)
        return attend(q, k, v)
    if impl == "ring":
        # the ring processes one mesh chunk per hop; there is no block knob
        return ring_attention(q, k, v, comm=comm, causal=causal)
    if impl == "ulysses":
        return ulysses_attention(
            q, k, v, comm=comm, causal=causal,
            block_size=512 if block_size is None else block_size,
        )
    return local_attention(
        q, k, v, causal=causal,
        block_size=512 if block_size is None else block_size,
    )


class MultiHeadAttention(nn.Module):
    """QKV projection → blockwise attention → output projection.

    ``(B, T, D_model)`` in and out; the attention core runs in
    ``(B, T, H, D_head)`` layout shared by every impl, so switching
    single-chip ↔ sequence-parallel changes no weights.
    """

    num_heads: int
    attn_impl: str = "local"
    causal: bool = True
    comm: Optional[Any] = None
    block_size: Optional[int] = None  # None = each impl's tuned default
    dtype: Any = jnp.float32
    # flash backward strategy (pallas_attention.flash_attention bwd_impl)
    flash_bwd_impl: str = "two_pass"

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        if d_model % self.num_heads:
            raise ValueError(f"d_model {d_model} not divisible by {self.num_heads} heads")
        d_head = d_model // self.num_heads
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (self.num_heads, d_head), axis=-1, use_bias=False,
            dtype=self.dtype, name=name,
        )
        q, k, v = dense("query")(x), dense("key")(x), dense("value")(x)
        o = _attend(
            q, k, v, impl=self.attn_impl, causal=self.causal, comm=self.comm,
            flash_bwd_impl=self.flash_bwd_impl,
            block_size=self.block_size,
        )
        return nn.DenseGeneral(
            d_model, axis=(-2, -1), use_bias=False, dtype=self.dtype, name="out"
        )(o)


class TransformerBlock(nn.Module):
    """Pre-LN residual block: x + attn(LN(x)); x + swiglu(LN(x))."""

    num_heads: int
    mlp_ratio: float = 4.0
    attn_impl: str = "local"
    causal: bool = True
    comm: Optional[Any] = None
    block_size: Optional[int] = None  # None = each impl's tuned default
    dtype: Any = jnp.float32
    flash_bwd_impl: str = "two_pass"

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        x = x + MultiHeadAttention(
            self.num_heads, self.attn_impl, self.causal, self.comm,
            self.block_size, self.dtype, self.flash_bwd_impl, name="attn",
        )(h)
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        d_ff = int(d_model * self.mlp_ratio)
        gate = nn.Dense(d_ff, use_bias=False, dtype=self.dtype, name="gate")(h)
        up = nn.Dense(d_ff, use_bias=False, dtype=self.dtype, name="up")(h)
        h = nn.silu(gate) * up  # SwiGLU: two MXU GEMMs + one VPU fuse
        return x + nn.Dense(d_model, use_bias=False, dtype=self.dtype, name="down")(h)


class TransformerLM(nn.Module):
    """Causal LM: token embedding → blocks → final LN → tied-untied logits."""

    vocab_size: int
    d_model: int
    num_heads: int
    num_layers: int
    max_len: int = 2048
    mlp_ratio: float = 4.0
    attn_impl: str = "local"
    comm: Optional[Any] = None
    block_size: Optional[int] = None  # None = each impl's tuned default
    remat: bool = False  # checkpoint each block: O(L) -> O(1) activations
    # None = full recompute; "dots" = save MXU dot outputs and recompute
    # only the cheap elementwise ops (jax.checkpoint_policies.
    # dots_with_no_batch_dims_saveable) — usually faster when HBM allows
    remat_policy: Optional[str] = None
    dtype: Any = jnp.float32
    flash_bwd_impl: str = "two_pass"

    @nn.compact
    def __call__(self, tokens):
        if tokens.shape[-1] > self.max_len:
            # nn.Embed's gather would silently clamp positions past the
            # table instead of erroring
            raise ValueError(
                f"sequence length {tokens.shape[-1]} exceeds max_len {self.max_len}"
            )
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype, name="embed")(tokens)
        pos = nn.Embed(self.max_len, self.d_model, dtype=self.dtype, name="pos")(
            jnp.arange(tokens.shape[-1])
        )
        x = x + pos[None]
        # rematerialization trades backward-pass FLOPs for activation
        # memory — the standard long-context recipe (HBM is the bottleneck)
        if self.remat:
            if self.remat_policy == "dots":
                import jax

                block_cls = nn.remat(
                    TransformerBlock,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                )
            else:
                block_cls = nn.remat(TransformerBlock)
        else:
            block_cls = TransformerBlock
        for i in range(self.num_layers):
            x = block_cls(
                self.num_heads, self.mlp_ratio, self.attn_impl, True,
                self.comm, self.block_size, self.dtype,
                self.flash_bwd_impl, name=f"block{i}",
            )(x)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        return nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head")(x)
