"""Device solve: of the host's wait for a solve's result
(``solve_wait_ms``), the part after the device had finished the solve:
the transfer of the packed view (``solve_readback_mb``) and the host's
wake-up, against the rest, which is the device still computing. Per
``ops.solve_readback`` event on the traced tail's host plane, its end
minus the end of the last ``jit__ell_reconverge`` launch before it, both
on the profiler's own clock (``chipbench/hoststage.py``); median.
Nothing untraced, or where the tail ran no ELL solve."""
from chipbench import hoststage


def read(record):
    return hoststage.readback_after_device_ms(record)
