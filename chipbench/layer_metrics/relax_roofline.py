"""Kernels: ``jit__ell_reconverge``'s share of its roofline, in percent,
at the passes the solves really ran.

As ``solve_roofline``, but the least time is computed
(chipbench/roofline.py) for the window's mean relax passes per solve,
read off the program's observation ``ops.ell.relax_passes``, plus the
one pass that builds the cold init, instead of the fixed 2 of a warm
solve that changes nothing: at 198 passes that least time is a hundredth
of the truth. The mean is over the whole window, the device time over
its traced part. Real edges are counted, not the padded slots the
program streams, so the share is, if anything, understated. Nothing from
a program that does not observe its passes."""
from chipbench import roofline

MODULE = "jit__ell_reconverge"


def read(record):
    dev = record.device
    solves = record.counter("ops.ell.relax_passes.count")
    if dev is None or not solves:
        return None
    measured, runs = 0.0, 0
    for module, (seconds, n) in dev.module_seconds(dev.steady).items():
        if module.startswith(MODULE):
            measured += seconds
            runs += n
    if not measured:
        return None
    passes = record.counter("ops.ell.relax_passes.sum") / solves + 1.0
    sh = record.shapes
    ops, nbytes = roofline.ell_reconverge(
        sh["nodes"], 2 * sh["links"],
        roofline.batch_rows(sh["vantage_degree"]), passes=passes)
    least = roofline.least_seconds(ops, nbytes, record.device_kind)[0]
    return 100.0 * runs * least / measured
