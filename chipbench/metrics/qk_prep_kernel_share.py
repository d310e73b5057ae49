"""Share of the traced query and key passes of the attention layers (head
norm, rotary, the cast and the flash kernels' layout) that took the single
kernel each way (the program's counters ``attn.qk_prep.kernel`` over
``attn.qk_prep.kernel`` + ``attn.qk_prep.xla``, one count a traced query or key
pass): 1.0 where ``pallas_qk_prep.takes_kernel`` admits the layers' shapes on
this backend, 0.0 where XLA's passes run. A program without the counters (a
parent commit) reads None."""

from chipbench.lm_trace import counter


def read(reading):
    passes = {form: counter(f"attn.qk_prep.{form}") for form in ("kernel", "xla")}
    if all(n is None for n in passes.values()):
        return None
    passes = reading.notes["qk_prep_passes"] = {form: n or 0 for form, n in passes.items()}
    return passes["kernel"] / (passes["kernel"] + passes["xla"])
