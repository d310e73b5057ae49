"""Per call: device time under the scopes ``mla.down`` (``W_qa``, ``W_kva``, the
two latent norms) and ``mla.up`` (``W_qb``, ``W_kvb``), all passes."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.tag_ms(reading, "latent_proj")
