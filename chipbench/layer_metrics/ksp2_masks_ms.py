"""KSP2 engine: per rebuild window, the sum of ``masks_ms`` as its
``ops.ksp2_masked_solve`` spans say it: the host time of
``_batch_masks``, the exclusion sets' slots looked up (or found held)
and the per-band bool masks built, padded to the batch's bucket; a part
of ``ksp2_masked_solve_ms``, which is that span's self time. Median over
the windows that solved a masked batch. Nothing from a program whose
span does not say."""
from chipbench import hoststage


def read(record):
    return hoststage.window_attr(record, "ops.ksp2_masked_solve", "masks_ms")
