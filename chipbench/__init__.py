"""The benchmark of record for openr-tpu (see chipbench/README.md).

Everything that decides a number lives here, where a PR that claims a
gain cannot change it: topology and traffic generation, the open-loop
clock, the reduction from spans, counters and the profiler's trace to
metrics, the table of peaks, the operations-and-bytes functions, the
plain reference and the comparison that decides ``correct``. From the
program it takes the system under test (``openr_tpu``), its wire types,
its spans and counters, and the names of its XLA modules.
"""
