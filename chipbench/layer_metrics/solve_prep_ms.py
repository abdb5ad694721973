"""Device solve: median of ``host_overhead_ms`` as the program's
``ops.ell_reconverge`` spans say it: the part of ``solve_span_ms`` ahead
of the dispatch, on the host (the overload sync and the journal,
``band_patch_inputs``' scatter triples a band, ``_emit_changes``, the
increase list's padding, the source ids' put). ``solve_prep_ms`` +
``solve_put_ms`` + ``solve_launch_ms`` = the span less two registry
observes. Nothing where the window ran no ELL solve."""
from chipbench import spanattr


def read(record):
    return spanattr.median(record, "ops.ell_reconverge", "host_overhead_ms")
