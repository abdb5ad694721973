"""The slowest decile's account, masked solves: the per-trace sum of
``ops.ksp2_masked_solve``, the second-path traces nested in it included
(0 for a trace without one), its median over the slowest tenth of the
window's traces minus its median over all of them
(``chipbench/hoststage.py``): masks, masked batches on the device, their
readback and the traces off them. Nothing under 200 traces."""
from chipbench import hoststage


def read(record):
    return hoststage.tail_excess_ms(record, "ops.ksp2_masked_solve")
