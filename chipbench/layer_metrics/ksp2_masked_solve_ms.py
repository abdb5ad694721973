"""KSP2 engine: per rebuild window, the self time of its
``ops.ksp2_masked_solve`` spans (mask build, dispatch and readback of
the masked batches with fresh masks, for the destinations whose first
paths moved; the second-path traces nested in the span are
``ksp2_trace_ms``'s); median over the windows that have one. Nothing
from a program that has no such span."""
from chipbench import spantree


def read(record):
    return spantree.median_self_ms(record, "ops.ksp2_masked_solve")
