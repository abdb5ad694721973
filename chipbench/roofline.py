"""Operations and bytes a solve needs, from its shapes, and the least
time a chip could take for them.

Both formulations are Bellman-Ford over int32 distances for a batch of
``batch`` source rows (the vantage and its neighbours, padded to a
power of two, at least 8):

- dense (``ops/spf.py``, ``jit__spf_view_batch``): each pass computes
  ``min_k d[s,k] + t[k,j]`` over an ``n x n`` matrix: 2*batch*n*n
  operations, and the matrix (4*n*n bytes) streams from memory once,
  with the distance rows read and written (8*batch*n). From direct
  edges, a node ``h`` links away is final after ``h - 1`` passes, and
  one more pass sees that nothing changed: ``passes`` = the largest
  fewest-links count among shortest paths (chipbench/reference.py).
- sliced ELL (``ops/spf_sparse.py``, ``jit__ell_reconverge``): each
  pass gathers one distance per directed edge and reduces per node:
  2*batch*edges operations; the edge slots (source index and weight,
  8 bytes each) stream once and the rows are read and written. A warm
  solve seeded with the previous distances needs the seeding pass and
  one pass that sees nothing change: 2 passes at least.

The packed result (distances and first-hop bits, 8*batch*n bytes) is
written once. Peaks come from ``peaks.json`` by ``device_kind``; an
unknown kind is an error. The operations are int32 adds and mins, which
run on the vector unit; the table's bf16 matrix peak is the only
published compute peak, so the compute bound is generous and the share
is, if anything, understated — at these shapes memory bounds both.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple


def batch_rows(vantage_degree: int) -> int:
    bucket = 8
    while bucket < vantage_degree + 1:
        bucket *= 2
    return bucket


def dense_view_batch(nodes: int, batch: int, passes: int) -> Tuple[float, float]:
    """(operations, bytes)"""
    per_pass_ops = 2.0 * batch * nodes * nodes
    per_pass_bytes = 4.0 * nodes * nodes + 8.0 * batch * nodes
    return passes * per_pass_ops, passes * per_pass_bytes + 8.0 * batch * nodes


def ell_reconverge(nodes: int, directed_edges: int, batch: int,
                   passes: int = 2) -> Tuple[float, float]:
    """(operations, bytes)"""
    per_pass_ops = 2.0 * batch * directed_edges
    per_pass_bytes = 8.0 * directed_edges + 8.0 * batch * nodes
    return passes * per_pass_ops, passes * per_pass_bytes + 8.0 * batch * nodes


def peaks(device_kind: str) -> Dict[str, float]:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def least_seconds(ops: float, nbytes: float, device_kind: str
                  ) -> Tuple[float, str]:
    """The least time for the call, and which peak bounds it."""
    peak = peaks(device_kind)
    compute = ops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute > memory else (memory, "memory")
