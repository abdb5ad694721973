"""KSP2 engine: per rebuild window, the self time of its
``decision.ksp2_recompute`` spans: ``_recompute`` less the masked solves
(``ops.ksp2_masked_solve``) and the traces (``decision.ksp2_trace``)
nested in it, which is its bookkeeping: the candidate closure, the
before/after comparison of both path sets, ``_set_first_paths`` and the
exclusion sets' slots, the ``node_users`` index, ``_note_paths``.
Median over the windows that recomputed. Nothing from a program that has
no such span."""
from chipbench import hoststage, spantree


def read(record):
    return hoststage.window_ms(
        record, "decision.ksp2_recompute", spantree.self_ms)
