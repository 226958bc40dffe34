"""Operations and bytes of the kernels whose roofline share the
benchmark reports, counted from the work and not from the program.

PageRank over a CSR of V vertices and E edges, one iteration of
y[u] = sum over u's edges (u, v) of x[v] / deg(v), then the damping:
the least HBM traffic any implementation can make is one pass over the
edge targets (E int32) and the offsets (V + 1 int32), one read of x
(V float32) and one write of the new x (V float32).  The E gathers of
x[v] are not counted: x (4 bytes a vertex, 1 MiB at 2^18 vertices) fits
in the chip's on-chip memory, where a kernel can serve them without
touching HBM.  So no implementation can beat this count per iteration
unless it keeps part of the edge targets on chip across iterations.
"""
from __future__ import annotations

INT32 = 4
FLOAT32 = 4


def pagerank_bytes(n_edges: int, n_vertices: int, iters: int) -> int:
    """Least HBM bytes of ``iters`` PageRank iterations over a CSR."""
    per_iter = (n_edges * INT32 + (n_vertices + 1) * INT32
                + 2 * n_vertices * FLOAT32)
    return iters * per_iter
