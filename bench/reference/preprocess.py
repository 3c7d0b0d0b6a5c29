"""Plain preprocessing reference in numpy.

What ``PreprocessPipeline(variant="degree_sort", with_csc=False,
slack_headroom=h)`` promises, written out independently of it:

  degrees  — out-degree of every vertex (``bincount`` of src);
  new_ids  — new id of every old id: vertices in descending degree, ties
             in ascending old id (a stable sort);
  csr      — the relabelled edges grouped by relabelled src, each
             vertex's neighbours in edge-list order;
  slack    — each vertex's slab holds its degree plus
             ``max(min_slack, ceil(degree * h))`` slots, the neighbours
             first, then -1.

``csr_value_sorted`` is the control: neighbours sorted by value within
each vertex, which breaks the edge-list order the configuration states.
"""
from __future__ import annotations

import numpy as np

TOMBSTONE = -1


def degrees(src: np.ndarray, num_nodes: int) -> np.ndarray:
    return np.bincount(src, minlength=num_nodes).astype(np.int64)


def new_ids(deg: np.ndarray) -> np.ndarray:
    order = np.argsort(-deg, kind="stable")  # old ids in new order
    ids = np.empty(deg.shape[0], np.int64)
    ids[order] = np.arange(deg.shape[0])
    return ids


def csr(src: np.ndarray, dst: np.ndarray, num_nodes: int):
    """(offsets, neighs) of the edge list, neighbours in edge-list order."""
    offsets = np.zeros(num_nodes + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=num_nodes), out=offsets[1:])
    neighs = dst[np.argsort(src, kind="stable")]
    return offsets, neighs


def csr_value_sorted(src: np.ndarray, dst: np.ndarray, num_nodes: int):
    """The control: the same rows, each sorted by neighbour id."""
    offsets, _ = csr(src, dst, num_nodes)
    return offsets, dst[np.lexsort((dst, src))]


def slack(offsets: np.ndarray, neighs: np.ndarray, headroom: float, min_slack: int):
    """(offsets, neighs, counts) of the slack layout of a CSR."""
    deg = np.diff(offsets)
    cap = deg + np.maximum(min_slack, np.ceil(deg * headroom).astype(np.int64))
    soff = np.zeros(deg.shape[0] + 1, np.int64)
    np.cumsum(cap, out=soff[1:])
    slab = np.full(int(soff[-1]), TOMBSTONE, np.int64)
    # slot j of vertex v holds neighs[offsets[v] + j] for j < deg[v]
    owner = np.repeat(np.arange(deg.shape[0]), deg)
    rank = np.arange(neighs.shape[0]) - offsets[owner]
    slab[soff[owner] + rank] = neighs
    return soff, slab, deg


def mismatches(got, want) -> int:
    """Entries that differ; every entry when the lengths differ."""
    got = np.asarray(got)
    want = np.asarray(want)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int(np.count_nonzero(got != want))
