"""Kernels: the solve programs' share of their roofline, in percent.

Least time for the executions seen in the steady part of the trace
(chipbench/roofline.py, at this cell's shapes) over the device time of
those programs' events, matched by XLA module name. At both cells'
shapes memory is the bound (tests/chipbench pins that)."""
from chipbench import roofline

# XLA module name (as the program's jit names it) -> its cost function
SOLVES = {
    "jit__spf_view_batch": lambda sh, b: roofline.dense_view_batch(
        sh["nodes"], b, sh["relax_passes"]),
    "jit__ell_reconverge": lambda sh, b: roofline.ell_reconverge(
        sh["nodes"], 2 * sh["links"], b),
}


def read(record):
    dev = record.device
    if dev is None:
        return None
    sh = record.shapes
    batch = roofline.batch_rows(sh["vantage_degree"])
    least, measured = 0.0, 0.0
    for module, (seconds, runs) in dev.module_seconds(dev.steady).items():
        for name, cost in SOLVES.items():
            if module.startswith(name):
                ops, nbytes = cost(sh, batch)
                least += runs * roofline.least_seconds(
                    ops, nbytes, record.device_kind)[0]
                measured += seconds
    if not measured:
        return None
    return 100.0 * least / measured
