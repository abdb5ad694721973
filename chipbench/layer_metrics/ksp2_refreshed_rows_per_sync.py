"""KSP2 engine: masked rows a sync solved again only to keep them exact
(``refreshed_rows`` of ``decision.ksp2_sync``): where the engine's
walk-reach proof does not answer for a window (a drained node, parallel
links, no native tracer; until PR 39 every window that changed more
than one link), every row the tests did not name is re-solved and
nothing of it is traced. 0 where the window was proven; nearly every
destination where it was not. Median over the window's syncs. Nothing
from a program that does not say."""
from chipbench import spanattr


def read(record):
    return spanattr.median(record, "decision.ksp2_sync", "refreshed_rows")
