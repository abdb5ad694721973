"""Kernels: ``jit__ell_masked_source_batch``'s share of its roofline, in
percent: the least time (chipbench/roofline_ksp2.py, memory the bound)
for the destination rows the program's ``ops.ksp2_masked_solve`` spans
say it solved in the steady part of the traced window, over the device
time of that program's executions there. Real rows and real edges are
counted, not the padded batch and slots. Nothing where the program
never ran or the spans are absent."""
from chipbench import roofline, roofline_ksp2


def read(record):
    if record.device is None:
        return None
    measured, runs = roofline_ksp2.module_runs(record, roofline_ksp2.MASKED)
    rows = sum(s.attrs.get("rows", 0) for s in roofline_ksp2.steady_spans(
        record, "ops.ksp2_masked_solve"))
    if not runs or not rows:
        return None
    sh = record.shapes
    ops, nbytes = roofline_ksp2.masked_batch(
        sh["nodes"], 2 * sh["links"], rows, sh["ksp2_passes"] + 1.0)
    least = roofline.least_seconds(ops, nbytes, record.device_kind)[0]
    return 100.0 * least / measured
