"""KSP2 engine: per rebuild window, the time its ``decision.ksp2_diff``
spans took: what the window changed, read off the LinkState's journals
(``_journal_nodes``), the changed pairs against the engine's snapshot
(``_diff_pairs``) and the drain and label flips (``_diff_nodes``), at
the head of ``decision.ksp2_sync``; median over the windows that have
one. Nothing from a program that has no such span."""
from chipbench import hoststage


def read(record):
    return hoststage.window_ms(record, "decision.ksp2_diff")
