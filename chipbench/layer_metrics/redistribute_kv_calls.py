"""Redistribution: calls PrefixManager made to its KvStore client per
route update it redistributed (``prefixmgr.kvstore_calls`` /
``prefixmgr.redistribute_runs``): a sync of the whole table reads in the
thousands here, a delta about one where a prefix moved and 0 where none
did. Nothing from a program that does not count them."""


def read(record):
    runs = record.counter("prefixmgr.redistribute_runs")
    if not runs or "prefixmgr.kvstore_calls" not in record.counters:
        return None
    return record.counter("prefixmgr.kvstore_calls") / runs
