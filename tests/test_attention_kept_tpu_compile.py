"""Read from the two rematerialised train steps, compiled at their published
widths for a described TPU v5e, that every flash forward kernel is in the
program once: a block's checkpoint keeps the attention core's output and its
log-sum-exp (one float a row), so the backward pass runs the block again
without the kernel, and the step and the check's own program still fit the
chip with the kept arrays. A compile is not a run: nothing here is a time or
a result.

The steps are those the benchmark's own compile tests build (as
``chipbench/kinds/trinity_step.py`` and ``qnext_step.py`` build them, from the
cells' configurations); their fixtures skip where no topology can be described.
"""

import re

from tests.chipbench.test_chipbench_qnext_tpu_compile import compiled as qnext_compiled  # noqa: F401
from tests.chipbench.test_chipbench_trinity_tpu_compile import _total as total
from tests.chipbench.test_chipbench_trinity_tpu_compile import compiled, topo  # noqa: F401

GIB = 2**30


def kernels(text: str) -> dict:
    """How many Mosaic calls of each flash kernel the compiled text holds."""
    found = re.findall(r"^\s*%((?:swa|flash)_\w+?)(?:\.\d+)? = .*custom-call\(", text, re.M)
    return {name: found.count(name) for name in set(found)}


def test_the_trinity_step_runs_each_forward_kernel_once(compiled):  # noqa: F811
    """Six sliding layers and two full ones: a forward and a fused backward kernel each."""
    _, program, _ = compiled
    assert kernels(program.as_text()) == {
        "swa_fwd": 6, "swa_bwd_fused": 6, "flash_fwd": 2, "flash_bwd_fused": 2,
    }


def test_the_trinity_step_keeps_a_column_of_log_sum_exp_and_fits(compiled):  # noqa: F811
    _, program, evaluation_program = compiled
    text = program.as_text()
    # the kernels write and read the lane-broadcast layout; what lives from the forward pass to the backward
    # is its first lane, sliced out once a block in the forward pass (2 MB beside the output's 134 MB)
    assert "f32[1,32,16384,128]" in text
    columns = re.findall(
        r'= f32\[32,16384\]\S* reduce\(.*op_name="[^"]*?jvp\(lm\.body\)/TransformerLM/(block\d)/attn/attn\.\w+/slice"', text
    )
    assert sorted(columns) == [f"block{i}" for i in range(8)]
    # eight outputs and columns at most over the step that kept nothing (13.34 GiB, PR 32); with the
    # log-sum-exp kept as the kernel writes it (268 MB a block) it would be 3.2 GB over, and past the chip
    assert total(program.memory_analysis()) < min(14_318_943_744 + 8 * 136_314_880, 15 * GIB)
    assert total(evaluation_program.memory_analysis()) < 15 * GIB


def test_the_qwen3_next_step_runs_its_forward_kernel_once_and_fits(qnext_compiled):  # noqa: F811
    """One attention block a period of four: one kernel of each kind; the check's
    gradients still fit beside both AdamW moments (8 bytes a parameter)."""
    _, program, grads_program = qnext_compiled
    assert kernels(program.as_text()) == {"flash_fwd": 1, "flash_bwd_fused": 1}
    assert total(program.memory_analysis()) < 15 * GIB
    assert total(grads_program.memory_analysis()) + 8 * 625_667_136 < 15 * GIB
