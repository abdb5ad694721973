"""The slowest decile's account, KSP2 sync: the per-trace sum of
``decision.ksp2_sync`` (0 for a trace without one), its median over the
slowest tenth of the window's traces minus its median over all of them
(``chipbench/hoststage.py`` on ``chipbench/spantail.py``'s ranking). The
sync lies inside the debounce stage where it was staged and inside the
rebuild stage where it was not, so it says how much of
``tail_debounce_excess_ms`` + ``tail_rebuild_excess_ms`` is the
engine's. Nothing under 200 traces."""
from chipbench import hoststage


def read(record):
    return hoststage.tail_excess_ms(record, "decision.ksp2_sync")
