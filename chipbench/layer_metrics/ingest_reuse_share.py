"""Ingest: of the adjacencies in the ``adj:`` values Decision decoded in
the window, the share it took from the value it last decoded for the
same key because their bytes stood (``decision.adj_elements_reused``
over reused + ``decision.adj_elements_decoded``), in percent. A
publication re-encodes its node's whole database to change one
adjacency, so a fabric switch's 84-adjacency value reads ~99 and a grid
node that re-costs all of its 2-4 links reads 0. Nothing where the
window decoded no adjacency (a mix with no ``adj:`` key), or from a
program that does not keep the counters (one that decodes every value
whole)."""


def read(record):
    if "decision.adj_elements_reused" not in record.counters:
        return None
    reused = record.counter("decision.adj_elements_reused")
    seen = reused + record.counter("decision.adj_elements_decoded")
    return 100.0 * reused / seen if seen else None
