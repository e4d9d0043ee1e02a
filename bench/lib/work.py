"""The least work a correct solve must do, computed from the graph's shapes.

Whatever schedule, kernel or number of rounds implements it, a solve reads
every directed edge's ``(src, dst, w)`` once and writes every vertex's
distance, label and predecessor once, all four bytes wide.  Padding edges
are not counted.
"""

from __future__ import annotations

EDGE_BYTES = 3 * 4  # src int32, dst int32, w float32
VERTEX_BYTES = 3 * 4  # dist float32, lab int32, pred int32


def solve_min_bytes(n: int, directed_edges: int) -> int:
    return EDGE_BYTES * directed_edges + VERTEX_BYTES * n
