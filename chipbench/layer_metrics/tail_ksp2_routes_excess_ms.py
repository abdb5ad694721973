"""The slowest decile's account, the KSP2 per-prefix pass: the
per-trace sum of ``decision.ksp2_routes`` (0 for a trace without one),
its median over the slowest tenth of the window's traces minus its
median over all of them (``chipbench/hoststage.py``): the label-stack
routes re-derived for the destinations the engine named, inside the
rebuild stage. Nothing under 200 traces."""
from chipbench import hoststage


def read(record):
    return hoststage.tail_excess_ms(record, "decision.ksp2_routes")
