"""Kernels: ``jit__ell_all_view_rows``'s share of its roofline, in
percent, at the passes its fixed point really ran.

As ``ksp2_all_pairs_roofline``, but the least time is computed
(chipbench/roofline_ksp2.py) for the mean of the ``passes`` the
program's ``ops.ksp2_all_pairs`` spans carried out in the steady part of
the traced window, plus the one pass that builds the init, instead of
the fixed 2 of a warm solve that changes nothing: where a tight
increased edge restarts rows the fixed point runs the graph's diameter
in passes (60 on the 31x31 grid), and 2 passes are a thirtieth of the
truth. What ``relax_roofline`` is to ``solve_roofline``. Real nodes and
real edges are counted, not padded rows and slots, so the share is, if
anything, understated. Nothing where the program never ran, or from a
program that does not carry its pass count out."""
from chipbench import roofline, roofline_ksp2


def read(record):
    if record.device is None:
        return None
    measured, runs = roofline_ksp2.module_runs(
        record, roofline_ksp2.ALL_PAIRS)
    ran = [
        s.attrs["passes"] for s in roofline_ksp2.steady_spans(
            record, "ops.ksp2_all_pairs")
        if "passes" in s.attrs
    ]
    if not measured or not ran:
        return None
    sh = record.shapes
    ops, nbytes = roofline_ksp2.all_pairs(
        sh["nodes"], 2 * sh["links"], passes=sum(ran) / len(ran) + 1.0)
    least = roofline.least_seconds(ops, nbytes, record.device_kind)[0]
    return 100.0 * runs * least / measured
