"""KSP2 engine: relax passes a masked batch ran (``passes`` of
``ops.ksp2_masked_solve``: the most any batch of the span ran, off the
``while_loop``'s own counter, carried out with the rows). Every row of
a masked batch starts cold, so this is the vantage's hop eccentricity in
the deepest masked graph of the batch (the pass that builds the init
reaches one hop and is not counted, the one that finds nothing left
is): 4 to 7 on a fat-tree, 60 and more from a corner of the 31x31
grid. Median over the window's spans. Nothing from a program that does
not carry the count out."""
from chipbench import spanattr


def read(record):
    return spanattr.median(record, "ops.ksp2_masked_solve", "passes")
