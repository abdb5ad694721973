"""The slowest decile's account, ``_recompute``'s bookkeeping: the
per-trace self time of ``decision.ksp2_recompute`` (0 for a trace
without one; the masked solves and traces nested in it are not its),
its median over the slowest tenth of the window's traces minus its
median over all of them (``chipbench/hoststage.py``). Nothing under 200
traces, or from a program that has no such span."""
from chipbench import hoststage, spantree


def read(record):
    return hoststage.tail_excess_ms(
        record, "decision.ksp2_recompute", spantree.self_ms)
