"""KSP2 engine: per rebuild window, the time its
``decision.ksp2_walk_proof`` spans took: ``_second_paths_may_move``, the
trace arrays' patch and the walk-reach proof over the masked rows the
engine holds, run on the host inside ``ops.ksp2_all_pairs`` while the
rows solve is in flight; ``ksp2_all_pairs_ms`` less this is the dispatch
and the blocked part of the reap. Median over the windows that have one.
Nothing from a program that has no such span."""
from chipbench import hoststage


def read(record):
    return hoststage.window_ms(record, "decision.ksp2_walk_proof")
