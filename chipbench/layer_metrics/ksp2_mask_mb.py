"""KSP2 engine: the masks a masked solve hands to the device, in
megabytes (10**6 bytes): median of ``mask_bytes`` over the window's
``ops.ksp2_masked_solve`` spans, the per-band ``[bucket, rows, k]`` bool
masks of all the span's batches, which cross host to device with every
dispatch whatever the exclusion sets hold. Nothing from a program whose
span does not say."""
from chipbench import spanattr


def read(record):
    nbytes = spanattr.median(record, "ops.ksp2_masked_solve", "mask_bytes")
    return None if nbytes is None else nbytes / 1e6
