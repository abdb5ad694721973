"""Kernels: the share of its roofline of the fused program a KSP2 sync
dispatches, ``jit__ell_all_view_rows`` (the all-pairs solve, the view
and the endpoint rows), in percent: least time by
chipbench/roofline_ksp2.py over the device time of the executions in
the steady part of the traced window. Nothing where the program never
ran."""
from chipbench import roofline, roofline_ksp2


def read(record):
    if record.device is None:
        return None
    measured, runs = roofline_ksp2.module_runs(
        record, roofline_ksp2.ALL_PAIRS)
    if not measured:
        return None
    sh = record.shapes
    least = roofline.least_seconds(
        *roofline_ksp2.all_pairs(sh["nodes"], 2 * sh["links"]),
        record.device_kind)[0]
    return 100.0 * runs * least / measured
