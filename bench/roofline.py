"""Compulsory HBM bytes of the benchmark's jobs, from their shapes alone.

The counts do not depend on which kernel does the work (the Pallas
C-Buffer kernel, an XLA scatter, a binned two-phase reduce or a kernel a
later change brings): they are the bytes any implementation has to move
at least once, so a roofline share built on them compares every
implementation against the same floor.

They assume the input the benchmark hands the program: an uncompressed
edge list of int32 (src, dst) pairs, and float32 ranks. A later layout
that stores edges compressed reads fewer bytes than counted here, and
needs a change of this file, in a change that defines the benchmark, to
be counted fairly.
"""
from __future__ import annotations

EDGE_BYTES = 8  # one int32 src and one int32 dst
VALUE_BYTES = 4  # one float32 rank or contribution
VERTEX_VECTORS = 3  # rank read, out-degree read, result written


def pagerank_iteration_bytes(num_nodes: int, num_edges: int) -> int:
    """One push iteration: the edge stream read once (8 B/edge), the
    gathered contribution of each edge (4 B/edge), and the per-vertex
    rank, out-degree and result vectors (3 x 4 B/vertex)."""
    return num_edges * (EDGE_BYTES + VALUE_BYTES) + num_nodes * VERTEX_VECTORS * VALUE_BYTES


def pagerank_bytes(num_nodes: int, num_edges: int, iterations: int) -> int:
    """``iterations`` push iterations over the same graph."""
    return iterations * pagerank_iteration_bytes(num_nodes, num_edges)


def roofline_share(bytes_moved: float, seconds: float, hbm_bytes_per_s: float) -> float:
    """Percent of the bandwidth roofline: the least time the chip could
    take to move ``bytes_moved`` over the ``seconds`` it took. Raises on a
    non-positive time rather than report a share of nothing."""
    if seconds <= 0:
        raise ValueError(f"roofline share needs a positive time, got {seconds}")
    return 100.0 * bytes_moved / (hbm_bytes_per_s * seconds)
