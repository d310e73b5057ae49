"""Share of the traced Gated DeltaNet mixer passes whose work before the rule
(convolution, SiLU, the l2 norms) took the single kernel each way (the
program's counters ``gdn.conv.kernel`` over ``gdn.conv.kernel`` +
``gdn.conv.xla``, one count a mixer pass traced): 1.0 where
``pallas_gdn_conv.takes_kernel`` admits the mixers' shapes on this backend,
0.0 where XLA's passes run. A program without the counters (a parent commit)
reads None."""

from chipbench.lm_trace import counter


def read(reading):
    passes = {form: counter(f"gdn.conv.{form}") for form in ("kernel", "xla")}
    if all(n is None for n in passes.values()):
        return None
    passes = reading.notes["gdn_conv_passes"] = {form: n or 0 for form, n in passes.items()}
    return passes["kernel"] / (passes["kernel"] + passes["xla"])
