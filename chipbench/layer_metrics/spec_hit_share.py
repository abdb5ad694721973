"""Debounce: the share of the window's rebuilds that found the root's
view already solved, staged under the policy wait by the publication
that opened the debounce window (``ops.spec_hits`` over
``decision.route_build_runs``), in percent. 0 from a program that never
stages a view (its counter does not move, or is not there); nothing
where no rebuild ran."""


def read(record):
    rebuilds = record.counter("decision.route_build_runs")
    if not rebuilds:
        return None
    return 100.0 * record.counter("ops.spec_hits") / rebuilds
