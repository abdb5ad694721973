"""Debounce: per rebuild window, the time its ``ops.ell_scatter`` spans
took: ``EllState.apply_patch`` whole, the overload mask's sync, the
warm-start journal, and per band that changed one ``jit_patch`` launch
fed the host row blocks, or a widened band's re-upload; median over the
windows that have one. The other half of ``prewarm_ms`` beside
``ell_patch_host_ms``. Nothing from a program that has no such span."""
from chipbench import hoststage


def read(record):
    return hoststage.window_ms(record, "ops.ell_scatter")
