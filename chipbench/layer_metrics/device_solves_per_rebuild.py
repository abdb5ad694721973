"""Device solve: view solves dispatched to the device per rebuild
(``decision.device_solves`` over ``decision.route_build_runs``). 1 where
every event changes the graph, 0 where the traffic bypasses the solver;
nothing from a program that does not count them."""


def read(record):
    rebuilds = record.counter("decision.route_build_runs")
    if "decision.device_solves" not in record.counters or not rebuilds:
        return None
    return record.counter("decision.device_solves") / rebuilds
