"""Reading a compiled module's text (``compiled.as_text()``): questions
asked of ``hlo_collectives.instructions``, the one reader of it."""

from megatron_llm_tpu.hlo_collectives import instructions


def sorts_and_their_guards(hlo_text):
    """Of a compiled module's text: for each ``sort`` instruction, whether
    the computation that holds it is reached only through a
    ``conditional``'s branch."""
    return ["conditional" in row["under"] for row in instructions(hlo_text)
            if row["opcode"] == "sort"]


def lowered_text(lowered):
    """The text of a LOWERED program (``jax.jit(f).lower(...)``: the
    source's own operations, nothing fused yet) in the form
    ``instructions`` reads; ``lowered.as_text(dialect="hlo")`` prints a
    computation's header without its signature, which it does not."""
    return lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string()

