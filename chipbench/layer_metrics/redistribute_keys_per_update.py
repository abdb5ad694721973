"""Redistribution: keys set or cleared in KvStore per route update
PrefixManager redistributed (``prefixmgr.redistributed_keys`` +
``prefixmgr.withdrawn_keys`` over ``prefixmgr.redistribute_runs``): one
where every event toggles a prefix, 0 where events move adjacencies.
Nothing from a program that does not count them."""


def read(record):
    runs = record.counter("prefixmgr.redistribute_runs")
    if not runs:
        return None
    return (record.counter("prefixmgr.redistributed_keys")
            + record.counter("prefixmgr.withdrawn_keys")) / runs
