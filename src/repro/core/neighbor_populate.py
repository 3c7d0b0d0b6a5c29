"""Neighbor-Populate: Edgelist(COO) -> CSR (paper Algorithm 1 / 2).

This is the paper's representative pre-processing kernel. Its updates are
NON-commutative (the order of appends determines neighbor-array slots),
yet PB applies because the kernel permits *unordered parallelism*: a
vertex's neighbor list may appear in any order as long as every edge
lands exactly once.

Variants:
  * ``build_csr_oracle``    — sequential numpy semantics (tests only):
                              literal Algorithm 1 (EL order preserved).
  * ``build_csr_baseline``  — direct single-shot build: one stable sort
                              over the full 32-bit src key. On a parallel
                              machine with no atomics this *is* the
                              baseline; its locality is poor because the
                              key range is the whole vertex set.
  * ``build_csr_pb``        — Algorithm 2: coarse Binning at ``bin_range``
                              then per-bin fine grouping (Bin-Read).
  * ``build_csr_cobra``     — hierarchical (knob-free) COBRA execution.
  * ``build_csr_sharded``   — mesh-distributed Algorithm 2 (DESIGN.md §9).

``build_csr`` dispatches on a method name; ``build_csc`` builds the
transposed layout (in-neighbors — what pull kernels consume) through the
same dispatch via ``transpose_coo``, and ``build_csr_csc`` builds both
layouts of one graph: one binned stream per direction (the src-keyed
stream yields the CSR, the dst-keyed stream the CSC), one degree pass
each, shared relabeled input (DESIGN.md §10.2).

All Binning goes through the shared ``core.executor`` layer (DESIGN.md
§3); this module only states the *stream* (edges keyed by src vertex)
and the Bin-Read that follows. Degree counting is a commutative PB
reduction and routes through ``PBExecutor.reduce_stream`` — the method
(fused vs two-phase) is *decided*, never hardcoded, so the fused
accumulator legality of DESIGN.md §8.1 is enforced here too.

All variants produce a CSR whose per-vertex neighbor *sets* are equal;
baseline/pb/cobra additionally preserve EL order within each vertex
(stability), matching the oracle exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.executor import execute_binning, get_default_executor
from repro.core.graph import (
    COO, CSR, SlackCSR, offsets_from_degrees, transpose_coo,
)
from repro.core.plan import CobraPlan


def _degrees(src, num_nodes) -> jnp.ndarray:
    """Degree counting IS a commutative PB reduction (add of ones), so it
    routes through the executor's reduce path — ``decide`` picks fused
    only when the dense accumulator fits (DESIGN.md §8.1); oversized
    domains fall back to the two-phase tree. The neighbor *placement*
    that follows is order-sensitive and stays two-phase."""
    return get_default_executor().reduce_stream(
        src, jnp.ones(src.shape, jnp.int32), out_size=num_nodes, op="add"
    )


def build_csr_oracle(coo: COO) -> CSR:
    """Literal Algorithm 1 in numpy (sequential semantics). Test oracle."""
    src = np.asarray(coo.src)
    dst = np.asarray(coo.dst)
    n = coo.num_nodes
    degrees = np.bincount(src, minlength=n)
    offsets = np.concatenate([[0], np.cumsum(degrees)]).astype(np.int32)
    cursor = offsets[:-1].copy()
    neighs = np.zeros(src.shape[0], dtype=np.int32)
    for s, d in zip(src, dst):
        neighs[cursor[s]] = d
        cursor[s] += 1
    return CSR(jnp.asarray(offsets), jnp.asarray(neighs), n)


@functools.partial(jax.jit, static_argnames=("num_nodes",))
def _baseline(src, dst, num_nodes):
    degrees = jnp.bincount(src, length=num_nodes).astype(jnp.int32)
    offsets = offsets_from_degrees(degrees)
    perm = jnp.argsort(src, stable=True)  # full-key-range stable sort
    return offsets, jnp.take(dst, perm)


def build_csr_baseline(coo: COO) -> CSR:
    offsets, neighs = _baseline(coo.src, coo.dst, coo.num_nodes)
    return CSR(offsets, neighs, coo.num_nodes)


@functools.partial(
    jax.jit, static_argnames=("num_nodes", "bin_range", "method", "block", "plan")
)
def _pb_build(src, dst, degrees, num_nodes, bin_range, method="sort", block=2048, plan=None):
    offsets = offsets_from_degrees(degrees)
    num_bins = -(-num_nodes // bin_range)
    # Phase 1: Binning (coarse range) through the shared executor core.
    # Stable: in-bin stream order kept.
    bins = execute_binning(
        src, dst, bin_range=bin_range, num_bins=num_bins, method=method,
        plan=plan, block=block,
    )
    # Phase 2: Bin-Read — group by exact src *within* the binned stream.
    # Because the stream is already grouped at bin granularity, this pass's
    # random accesses span only one bin range at a time (the locality PB
    # buys). Functionally: a second stable partition by the fine key.
    perm = jnp.argsort(bins.idx, stable=True)
    neighs = jnp.take(bins.val, perm)
    return offsets, neighs


def build_csr_pb(
    coo: COO,
    bin_range: int | None = None,
    method: str = "sort",
    block: int = 2048,
    degrees: jnp.ndarray | None = None,
) -> CSR:
    """Algorithm 2 EL->CSR (paper Table 1's NeighPop row). ``method`` is
    any executor method, or "auto" to let the executor decide; a ``None``
    bin_range asks the executor for the planned range. ``degrees`` skips
    the degree pass when the caller already holds the src histogram (the
    preprocessing pipeline shares its stage-1 pass this way)."""
    if method == "auto" or bin_range is None:
        d = get_default_executor().decide(
            coo.num_nodes, coo.num_edges, coo.src.dtype, bin_range=bin_range
        )
        method = d.method if method == "auto" else method
        bin_range = d.bin_range
    plan = None
    if method == "hierarchical":
        plan = CobraPlan.from_hardware(coo.num_nodes, final_bin_range=bin_range)
        bin_range = plan.final_bin_range
    if degrees is None:
        degrees = _degrees(coo.src, coo.num_nodes)
    offsets, neighs = _pb_build(
        coo.src, coo.dst, degrees, coo.num_nodes, bin_range, method=method,
        block=block, plan=plan,
    )
    return CSR(offsets, neighs, coo.num_nodes)


def build_csr_sharded(
    coo: COO, mesh=None, axis_name: str | None = None, capacity: int | None = None
) -> CSR:
    """Distributed Neighbor-Populate (DESIGN.md §9): the coarse Binning
    pass owner-routes edges by source vertex across the mesh — paper
    Algorithm 2 with the interconnect as the top C-Buffer level. The
    stable exchange preserves Edgelist order within each vertex, so the
    result matches ``build_csr_oracle`` exactly, like every other build
    variant. Pre-processing at scale: per-device HBM traffic over the
    edge stream drops with device count."""
    from repro.core.distributed_pb import shard_build_csr

    return shard_build_csr(coo, mesh, axis_name=axis_name, capacity=capacity)


def build_csr_cobra(
    coo: COO, plan: CobraPlan | None = None, degrees: jnp.ndarray | None = None
) -> CSR:
    """Knob-free COBRA build (paper §4): hierarchical executor method."""
    plan = plan or CobraPlan.from_hardware(coo.num_nodes)
    if degrees is None:
        degrees = _degrees(coo.src, coo.num_nodes)
    offsets, neighs = _pb_build(
        coo.src, coo.dst, degrees, coo.num_nodes, plan.final_bin_range,
        method="hierarchical", plan=plan,
    )
    return CSR(offsets, neighs, coo.num_nodes)


# ---------------------------------------------------------------------------
# Method dispatch + the dual-layout build (DESIGN.md §10.2).
# ---------------------------------------------------------------------------

BUILD_METHODS = ("baseline", "pb", "cobra", "sharded", "auto")


def build_csr(
    coo: COO,
    method: str = "auto",
    bin_range: int | None = None,
    block: int = 2048,
    mesh=None,
    axis_name: str | None = None,
    degrees: jnp.ndarray | None = None,
) -> CSR:
    """EL->CSR through one named build variant. ``auto`` is the
    executor-decided PB build; ``sharded`` distributes over ``mesh``
    (falling back to the single-device auto build without one).
    ``degrees`` (a precomputed src histogram) spares the PB builds their
    degree pass; the baseline and sharded paths compute their own."""
    if method in ("auto", "pb"):
        m = "auto" if method == "auto" else "sort"
        return build_csr_pb(
            coo, bin_range=bin_range, method=m, block=block, degrees=degrees
        )
    if method == "baseline":
        return build_csr_baseline(coo)
    if method == "cobra":
        plan = CobraPlan.from_hardware(coo.num_nodes, final_bin_range=bin_range)
        return build_csr_cobra(coo, plan, degrees=degrees)
    if method == "sharded":
        return build_csr_sharded(coo, mesh=mesh, axis_name=axis_name)
    raise ValueError(
        f"unknown build method: {method!r} (want one of {BUILD_METHODS})"
    )


def build_slack_csr(
    coo: COO,
    headroom: float = 0.25,
    min_slack: int = 4,
    method: str = "auto",
    bin_range: int | None = None,
    block: int = 2048,
    degrees: jnp.ndarray | None = None,
) -> SlackCSR:
    """EL->SlackCSR: the mutable layout ``core.updates`` edits in place
    (DESIGN.md §15). The packed CSR comes out of the same PB build as
    ``build_csr``; the re-slack (``SlackCSR.from_csr``) is one device
    program that scatters the arcs into slabs with ``headroom``
    fractional (min ``min_slack`` absolute) spare capacity per vertex."""
    csr = build_csr(
        coo, method=method, bin_range=bin_range, block=block, degrees=degrees
    )
    return SlackCSR.from_csr(csr, headroom=headroom, min_slack=min_slack)


def build_csc(
    coo: COO,
    method: str = "auto",
    bin_range: int | None = None,
    block: int = 2048,
    mesh=None,
    axis_name: str | None = None,
) -> CSR:
    """EL->CSC: the CSR of the transposed graph (in-neighbor lists —
    the layout pull kernels like ``pagerank_csr_pull`` consume). The
    dst-keyed edge stream runs the SAME PB pipeline as the CSR build;
    only the stream key flips (``transpose_coo``)."""
    return build_csr(
        transpose_coo(coo), method=method, bin_range=bin_range, block=block,
        mesh=mesh, axis_name=axis_name,
    )


def build_csr_csc(
    coo: COO,
    method: str = "auto",
    bin_range: int | None = None,
    block: int = 2048,
    mesh=None,
    axis_name: str | None = None,
):
    """Dual-layout build: ``(CSR, CSC)`` of one graph. Each direction is
    one binned stream (src-keyed for push, dst-keyed for pull) through
    the shared executor — so a pipeline that needs both layouts pays two
    single-sweep builds over the same Edgelist, not a build plus an
    ad-hoc transpose of the finished CSR (DESIGN.md §10.2)."""
    kw = dict(
        method=method, bin_range=bin_range, block=block, mesh=mesh,
        axis_name=axis_name,
    )
    return build_csr(coo, **kw), build_csc(coo, **kw)


def csr_equal_as_sets(a: CSR, b: CSR) -> bool:
    """Same graph irrespective of in-neighborhood order (unordered
    parallelism's allowed freedom). Vectorized: one segment-sort via
    ``np.lexsort`` on (vertex, neighbor) per side — no Python loop over
    vertices, so large-graph tests stay cheap."""
    ao, bo = np.asarray(a.offsets), np.asarray(b.offsets)
    if not np.array_equal(ao, bo):
        return False
    an, bn = np.asarray(a.neighs), np.asarray(b.neighs)
    if an.shape != bn.shape:
        return False
    # owning vertex of every neighbor slot; offsets are equal, so one
    # segment array serves both sides
    seg = np.repeat(np.arange(a.num_nodes), np.diff(ao))
    return np.array_equal(an[np.lexsort((an, seg))], bn[np.lexsort((bn, seg))])
