"""Bulk LSDB load -> the first routes in Fib."""


def read(record):
    return record.setup.get("cold_build_s")
