"""Debounce: publications carried per rebuild window."""


def read(record):
    rebuilds = record.counter("decision.route_build_runs")
    if not rebuilds:
        return None
    return record.counter("chipbench.published") / rebuilds
