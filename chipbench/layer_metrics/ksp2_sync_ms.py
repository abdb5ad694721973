"""KSP2 engine: median of the program's ``decision.ksp2_sync`` span, one
per rebuild: the engine brought to the new LSDB (diff of the changed
pairs, the fused all-pairs dispatch and its readback, the affected-set
tests, masked re-solves, traces, priming) or, cold, rebuilt whole.
Nothing from a program that has no such span."""


def read(record):
    return record.span_median("decision.ksp2_sync")
