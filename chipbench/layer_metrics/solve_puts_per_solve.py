"""Device solve: host arrays a warm ELL dispatch hands to the device,
median of ``puts`` as the program's ``ops.ell_reconverge`` spans say it
(PR 52): the scatter triple of every band that had rows to scatter or
whose no-op was not yet resident, a widened band's two tensors, the
source ids where they differ from the batch held, the overload mask
where it changed, and the increase triple. 3 in a window whose rows the
publication-time prewarm had scattered; 3 more for every band a fused
patch names. Each put is a host-to-device transfer of 4 to 4,096 bytes
at ~0.27 ms on the chip, inside ``solve_prep_ms`` / ``solve_put_ms``.
Nothing where the window ran no ELL solve, or from a program whose span
does not say."""
from chipbench import spanattr


def read(record):
    return spanattr.median(record, "ops.ell_reconverge", "puts")
