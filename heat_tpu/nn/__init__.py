"""heat_tpu.nn — data-parallel module wrappers + flax passthrough.

The reference mounts ``torch.nn`` behind a module-level ``__getattr__`` so
``ht.nn.Conv2d`` *is* ``torch.nn.Conv2d`` (reference heat/nn/__init__.py:19-31),
and adds its own :class:`DataParallel` wrappers on top. The TPU-native analog
passes through to **flax.linen** (``ht.nn.Dense``, ``ht.nn.Conv`` …) — the
module system of the JAX stack — with the distributed wrappers defined here.
"""

from . import functional
from .data_parallel import DataParallel, DataParallelMultiGPU
from .fsdp import FSDP
from .pipeline import Pipeline
from .transformer import (
    GatedShortConv,
    Latent,
    LatentAttention,
    MultiHeadAttention,
    TransformerBlock,
    TransformerLM,
    causal_lm_loss,
    exit_distribution,
    glm_4_7_flash,
    lfm2_24b_a2b,
    olmoe_1b_7b,
    ouro_2_6b,
    qwen3_next_80b_a3b,
    read_exits,
    trinity_mini,
)
from .deltanet import GatedDeltaNet, gated_delta_rule
from .moe import DroplessMoE, MoEMLP, balance_bias_rule, read_routing
from .quant_dense import QuantDense

__all__ = [
    "DataParallel",
    "DataParallelMultiGPU",
    "DroplessMoE",
    "FSDP",
    "GatedDeltaNet",
    "GatedShortConv",
    "Latent",
    "LatentAttention",
    "functional",
    "gated_delta_rule",
    "MoEMLP",
    "MultiHeadAttention",
    "Pipeline",
    "balance_bias_rule",
    "QuantDense",
    "TransformerBlock",
    "TransformerLM",
    "causal_lm_loss",
    "exit_distribution",
    "glm_4_7_flash",
    "lfm2_24b_a2b",
    "olmoe_1b_7b",
    "ouro_2_6b",
    "qwen3_next_80b_a3b",
    "read_exits",
    "read_routing",
    "trinity_mini",
]


def __getattr__(name):
    """Fall through to ``flax.linen`` for anything not defined here
    (reference heat/nn/__init__.py:19-31 does the same against torch.nn)."""
    import flax.linen as _linen

    try:
        return getattr(_linen, name)
    except AttributeError:
        raise AttributeError(
            f"module {name} not implemented in flax.linen or heat_tpu.nn"
        ) from None
