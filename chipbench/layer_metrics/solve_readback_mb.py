"""Device solve: what a solve hands back to the host, in megabytes
(10**6 bytes): the median over the window of the ``bytes`` attribute of
the program's ``ops.solve_readback`` spans, the size of the packed
result the host then waits for (``solve_wait_ms`` is how long). Today
that is the whole ``[2B, n_pad]`` int32 view, distances then first
hops, of every solve, so it grows with the fabric and not with what an
event changed: 2 x 16 x 4,992 x 4 = 0.64 MB at ``fabric-5000``,
2 x 16 x 50,304 x 4 = 6.4 MB at ``fabric-50k``. Nothing where the
window solved no view on the device, or from a program whose span does
not say."""
from chipbench import spanattr


def read(record):
    nbytes = spanattr.median(record, "ops.solve_readback", "bytes")
    return None if nbytes is None else nbytes / 1e6
