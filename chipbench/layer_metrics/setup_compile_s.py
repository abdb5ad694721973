"""Compile cache: seconds of backend compile (or cache retrieval)
during set-up, summed off ``jax.monitoring``."""


def read(record):
    return record.setup.get("compile_s")
