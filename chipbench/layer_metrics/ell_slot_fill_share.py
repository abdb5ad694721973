"""Kernels: of the slots every pass of the ELL relax streams, the share
that holds an edge, in percent: ``edges`` over ``slots`` as the
program's ``ops.ell_reconverge`` spans say them (the filled slots of
the resident bands, and the sum over bands of rows x k), each the
median over the window. A band is as wide as the power of two at or
above its widest row, so a fabric whose spines have 893 links pads them
to 1,024 and its 84-link switches to 128: 77.3% at ``fabric-50k``
(1,200,192 of 1,552,256), 72.8% at ``fabric-5000`` (112,896 of
155,136), 49.5% on the 100 x 100 grid (degree 2-4 in a band of 8). The rest
is padding the relax gathers and discards; a banding rule or a kernel
that skips it moves this, and through ``device_busy_ms`` the solve.
Nothing where the window ran no ELL solve, or from a program whose
span does not say."""
from chipbench import spanattr

SPAN = "ops.ell_reconverge"


def read(record):
    slots = spanattr.median(record, SPAN, "slots")
    edges = spanattr.median(record, SPAN, "edges")
    if not slots or edges is None:
        return None
    return 100.0 * edges / slots
