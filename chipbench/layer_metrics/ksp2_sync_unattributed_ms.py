"""KSP2 engine: per rebuild window, the self time of its
``decision.ksp2_sync`` spans: the sync less every span nested in it
(``decision.ksp2_diff``, ``ops.ksp2_all_pairs``,
``decision.ksp2_affected``, ``decision.ksp2_recompute``, a refresh's
``ops.ksp2_masked_solve``): the view batch, the unpacking of the rows,
``_preload_view``, the matrix dispatch where no masked span took it
(``matrix_dispatch_ms`` on the sync), ``_prime_all`` and the commit.
Median. Nothing from a program whose sync has no ``decision.ksp2_diff``
inside: there the same arithmetic reads another quantity."""
from chipbench import hoststage, spantree


def read(record):
    if record.span_median("decision.ksp2_diff") is None:
        return None
    return hoststage.window_ms(
        record, "decision.ksp2_sync", spantree.self_ms)
