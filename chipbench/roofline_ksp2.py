"""Operations and bytes the two KSP2 device solves need, from their
shapes, for ``roofline.least_seconds``; and the device time and the
executions of a program in the steady part of a traced window.

Both are Bellman-Ford over int32 distances on the sliced-ELL bands
(``ops/spf_sparse.py``), as ``roofline.ell_reconverge`` is: a pass
gathers one distance per directed edge and source row and reduces per
node, 2 operations an edge and row; the edge slots (source index and
weight, 8 bytes each) stream once a pass and the rows are read and
written (8 bytes a row and node). Real edges are counted, not the
padded slots the program streams, and the fewest passes the
formulation can take, so a share is, if anything, understated.

- the masked batch (``_ell_masked_fixed_point``, the program
  ``jit__ell_masked_source_batch``): ``rows`` single-source solves from the vantage, each over its own
  edge-masked graph, so a pass also reads one mask byte per edge and
  row. It starts cold: the pass that builds the init, then the
  vantage's hop eccentricity in passes (``reference.relax_passes``
  over the unmasked final graph: a masked graph needs as many or
  more). The rows are written once more as the result.
- the all-pairs solve (``_ell_fixed_point`` from every node, inside
  the fused program ``jit__ell_all_view_rows``): ``nodes`` source rows, warm-seeded from the previous event's
  resident matrix, so 2 passes at least (the seeding pass and the one
  that sees nothing change); the matrix is written once more.
"""

from __future__ import annotations

from typing import Tuple

MASKED = "jit__ell_masked_source_batch"
ALL_PAIRS = "jit__ell_all_view_rows"


def masked_batch(nodes: int, directed_edges: int, rows: int,
                 passes: float) -> Tuple[float, float]:
    """(operations, bytes)"""
    per_pass_ops = 2.0 * rows * directed_edges
    per_pass_bytes = (8.0 * directed_edges + 1.0 * rows * directed_edges
                      + 8.0 * rows * nodes)
    return passes * per_pass_ops, passes * per_pass_bytes + 4.0 * rows * nodes


def all_pairs(nodes: int, directed_edges: int,
              passes: float = 2.0) -> Tuple[float, float]:
    """(operations, bytes)"""
    per_pass_ops = 2.0 * nodes * directed_edges
    per_pass_bytes = 8.0 * directed_edges + 8.0 * nodes * nodes
    return passes * per_pass_ops, passes * per_pass_bytes + 4.0 * nodes * nodes


def module_runs(record, name: str) -> Tuple[float, int]:
    """(device seconds, executions) of the program whose XLA module
    name is exactly ``name`` in the steady part of the traced window;
    the trace appends the program's fingerprint in brackets."""
    dev = record.device
    seconds, runs = 0.0, 0
    for module, (s, n) in dev.module_seconds(dev.steady).items():
        if module.split("(", 1)[0] == name:
            seconds += s
            runs += n
    return seconds, runs


def steady_spans(record, name: str) -> list:
    """The program's spans ``name`` that began in the steady part of
    the traced window (spans are on the wall clock)."""
    dev = record.device
    t0 = record.steady_wall_s * 1e3
    t1 = t0 + (dev.steady[1] - dev.steady[0]) / 1e6
    return [s for s in record.spans
            if s.name == name and t0 <= s.ts_ms < t1]
