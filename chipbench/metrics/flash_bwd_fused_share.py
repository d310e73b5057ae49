"""Share of the traced flash backward calls that took the fused single-pass
kernel (the program's counters ``attn.bwd.fused`` over ``attn.bwd.fused`` +
``attn.bwd.two_pass``, one count a backward call traced, full and windowed
layers alike): 1.0 where the rule of ``pallas_attention._flash_bwd_dispatch``
admits every layer's shape, 0.0 where its resident blocks are past the chip's
VMEM and the dq and dk/dv passes run. A program without the counters (a parent
commit) reads None."""

from chipbench.lm_trace import counter


def read(reading):
    calls = {impl: counter(f"attn.bwd.{impl}") for impl in ("fused", "two_pass")}
    if all(n is None for n in calls.values()):
        return None
    calls = reading.notes["flash_bwd_calls"] = {impl: n or 0 for impl, n in calls.items()}
    return calls["fused"] / (calls["fused"] + calls["two_pass"])
