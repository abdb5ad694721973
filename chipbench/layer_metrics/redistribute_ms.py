"""Redistribution: a route update's arrival at PrefixManager -> the last
key it hands to KvStore; median of ``prefixmgr.redistribute`` over the
window's traces. Nothing from a program whose PrefixManager has no such
span."""


def read(record):
    return record.span_median("prefixmgr.redistribute")
