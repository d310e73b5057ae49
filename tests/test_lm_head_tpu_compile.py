"""Read from the published-width OLMoE train step, compiled for a described
TPU v5e, that the head's loss is one loop: the pass that forms a block's
logits forms both gradients from them, so the step holds one ``while`` whose
carry is the kernel's float32 gradient, three products a block with the
vocabulary in them, and no array of every position by the vocabulary. A
compile is not a run: nothing here is a time or a result.

The step is the one the benchmark's own compile test builds (as
``chipbench/kinds/lm_step.py`` builds it, from the cell's configuration); its
fixtures skip where no topology can be described.
"""

from tests.chipbench.test_chipbench_lm_tpu_compile import compiled, topo  # noqa: F401
from tests.test_olmoe import loops, products_over

GIB = 2**30


def test_the_step_holds_one_loop_whose_carry_is_the_kernels_gradient(compiled):  # noqa: F811
    _, program, grads_program = compiled
    for text in (program.as_text(), grads_program.as_text()):
        found = loops(text)
        assert len(found) == 1, found
        assert "f32[2048,50304]" in found[0]  # the (D, V) float32 sum over the blocks


def test_a_block_takes_three_products_with_the_vocabulary_in_them(compiled):  # noqa: F811
    """Logits, the hidden states' gradient, the kernel's gradient: the
    logits are not formed a second time."""
    _, program, _ = compiled
    products = products_over(program.as_text(), 50304)
    assert len(products) == 3, products


def test_no_array_of_every_position_by_the_vocabulary_and_the_step_fits(compiled):  # noqa: F811
    config, program, _ = compiled
    text = program.as_text()
    assert "[16384,50304]" not in text and "[4,4096,50304]" not in text and "[8,2048,50304]" not in text
    m = program.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert total < 15 * GIB
    assert abs(total - config["memory_analysis"]["total_bytes"]) < 0.01 * total
