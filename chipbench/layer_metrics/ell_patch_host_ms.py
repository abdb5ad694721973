"""Debounce: per rebuild window, the time its ``ops.ell_patch`` spans
took: the host's re-derivation of the changed rows of the resident ELL
bands (``LinkState.affected_since`` and ``spf_sparse.ell_patch``), under
``decision.prewarm`` on Decision's thread, or under ``graph.view_sync``
where no prewarm ran; median over the windows that have one. With
``ell_patch_scatter_ms`` it is what ``prewarm_ms`` is made of. Nothing
from a program that has no such span."""
from chipbench import hoststage


def read(record):
    return hoststage.window_ms(record, "ops.ell_patch")
