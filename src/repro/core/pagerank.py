"""PageRank — the paper's representative processing kernel.

Variants exercised by the benchmarks:

  * ``pagerank_coo_scatter``  — "processing the Edgelist directly"
    (paper Fig. 5 baseline): every iteration scatter-adds contributions
    at random destination order. Irregular, DRAM-latency bound.
  * ``pagerank_csr_pull``     — standard CSC/pull execution over a built
    CSR: per-vertex gather + segment sum (sequential neighbor arrays).
  * ``pagerank_pb``           — PB push execution: destinations are
    binned ONCE (pre-processing), then every iteration's scatter walks
    bin-sorted (near-sequential) destinations. This is where PB's
    per-iteration locality win comes from, and why PageRank amortizes
    Binning across iterations (paper Table 1 shows smaller but real
    gains vs. NeighPop's one-shot 6-7x).

PageRank updates are commutative, so bins may be read in any order and
in-bin coalescing (PHI-style) is legal; ``coalesce=True`` pre-reduces
duplicate destinations within the binned stream.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.core.executor import get_default_executor
from repro.core.graph import COO, CSR, degrees_from_coo, segment_ids_from_offsets


class PRResult(NamedTuple):
    ranks: jnp.ndarray
    iters: int


DAMP = 0.85


def _out_degrees(coo: COO) -> jnp.ndarray:
    return degrees_from_coo(coo, by="src")


@functools.partial(jax.jit, static_argnames=("num_nodes", "iters"))
def _pr_coo(src, dst, num_nodes, iters):
    n = num_nodes
    outdeg = jnp.maximum(jnp.bincount(src, length=n), 1).astype(jnp.float32)
    ranks = jnp.full((n,), 1.0 / n, dtype=jnp.float32)

    def body(_, ranks):
        contrib = ranks / outdeg
        # random-destination scatter: the Edgelist-direct execution
        incoming = jnp.zeros((n,), jnp.float32).at[dst].add(jnp.take(contrib, src))
        return (1.0 - DAMP) / n + DAMP * incoming

    return jax.lax.fori_loop(0, iters, body, ranks)


def pagerank_coo_scatter(coo: COO, iters: int = 10) -> PRResult:
    return PRResult(_pr_coo(coo.src, coo.dst, coo.num_nodes, iters), iters)


@functools.partial(jax.jit, static_argnames=("num_nodes", "iters", "num_edges"))
def _pr_pull(offsets_t, neighs_t, outdeg, num_nodes, num_edges, iters):
    """Pull over the transpose CSR (a CSC): for each v, sum contributions
    of in-neighbors, which are contiguous in memory."""
    n = num_nodes
    seg = segment_ids_from_offsets(offsets_t, num_edges)  # edge -> dst vertex
    ranks = jnp.full((n,), 1.0 / n, dtype=jnp.float32)

    def body(_, ranks):
        contrib = ranks / outdeg
        gathered = jnp.take(contrib, neighs_t)  # in-neighbor contributions
        incoming = compat.segment_sum(
            # sorted-ok: seg comes from segment_ids_from_offsets, which is
            gathered, seg, num_segments=n, indices_are_sorted=True
        )  # non-decreasing by construction (CSR offsets are monotone)
        return (1.0 - DAMP) / n + DAMP * incoming

    return jax.lax.fori_loop(0, iters, body, ranks)


def pagerank_csr_pull(csc: CSR, outdeg: jnp.ndarray, iters: int = 10) -> PRResult:
    r = _pr_pull(
        csc.offsets,
        csc.neighs,
        jnp.maximum(outdeg, 1).astype(jnp.float32),
        csc.num_nodes,
        csc.num_edges,
        iters,
    )
    return PRResult(r, iters)


@functools.partial(
    jax.jit, static_argnames=("num_nodes", "iters", "bin_range", "coalesce")
)
def _pr_pb(src_b, dst_b, num_nodes, iters, bin_range, coalesce):
    """PB push: (src,dst) stream pre-binned by dst//bin_range. Per
    iteration, contributions scatter into bin-sorted destinations."""
    n = num_nodes
    outdeg = jnp.maximum(jnp.bincount(src_b, length=n), 1).astype(jnp.float32)
    ranks = jnp.full((n,), 1.0 / n, dtype=jnp.float32)

    def body(_, ranks):
        contrib = ranks / outdeg
        vals = jnp.take(contrib, src_b)
        incoming = jnp.zeros((n,), jnp.float32).at[dst_b].add(vals)
        return (1.0 - DAMP) / n + DAMP * incoming

    return jax.lax.fori_loop(0, iters, body, ranks)


def pb_bin_edges(coo: COO, bin_range: int, method: str | None = None):
    """The PB pre-processing step for push PageRank (paper Table 1's
    PR row): bin edges by destination range once via the shared executor
    (DESIGN.md §3); iterations then scatter in near-sequential order.
    ``method=None`` lets the executor pick. Returns (src_binned,
    dst_binned)."""
    bins = get_default_executor().bin_stream(
        coo.dst, coo.src, num_indices=coo.num_nodes, bin_range=bin_range,
        method=method,
    )
    return bins.val, bins.idx


def pagerank_pb_prebinned(
    src_b, dst_b, num_nodes: int, iters: int = 10, bin_range: int = 1 << 14
) -> PRResult:
    """Processing phase only (binning amortized — paper Table 1's setup)."""
    r = _pr_pb(src_b, dst_b, num_nodes, iters, bin_range, False)
    return PRResult(r, iters)


def pagerank_pb(
    coo: COO, iters: int = 10, bin_range: int = 1 << 14, coalesce: bool = False
) -> PRResult:
    src_b, dst_b = pb_bin_edges(coo, bin_range)
    r = _pr_pb(src_b, dst_b, coo.num_nodes, iters, bin_range, coalesce)
    return PRResult(r, iters)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_nodes", "iters", "method", "bin_range", "num_bins", "block", "plan",
    ),
)
def _pr_fused(src, dst, num_nodes, iters, method, bin_range, num_bins, block, plan=None):
    """Fused PB push: every iteration bins AND accumulates contributions
    in one sweep of the edge stream (DESIGN.md §8) — no pre-binned
    (src, dst) copy is ever materialized, unlike ``_pr_pb``."""
    from repro.core.executor import execute_reduce

    n = num_nodes
    outdeg = jnp.maximum(jnp.bincount(src, length=n), 1).astype(jnp.float32)
    ranks = jnp.full((n,), 1.0 / n, dtype=jnp.float32)

    def body(_, ranks):
        contrib = ranks / outdeg
        incoming = execute_reduce(
            dst,
            jnp.take(contrib, src),
            out_size=n,
            op="add",
            method=method,
            bin_range=bin_range,
            num_bins=num_bins,
            plan=plan,
            block=block,
        )
        return (1.0 - DAMP) / n + DAMP * incoming

    return jax.lax.fori_loop(0, iters, body, ranks)


def pagerank_fused(coo: COO, iters: int = 10, method: str | None = None) -> PRResult:
    """PageRank through the executor's fused reduction (DESIGN.md §8):
    the commutative add lets each iteration's irregular update run as a
    single bin-and-accumulate sweep. ``method=None`` asks ``decide``
    (reduce candidate set); any ``REDUCE_METHODS`` entry forces a path.
    """
    ex = get_default_executor()
    d = ex.decide_or_forced(
        method, coo.num_nodes, coo.num_edges, jnp.float32, kind="reduce"
    )
    r = _pr_fused(
        coo.src, coo.dst, coo.num_nodes, iters, d.method, d.bin_range,
        d.num_bins, ex.block, d.plan,
    )
    return PRResult(r, iters)


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "method", "bin_range", "num_bins", "block", "plan"),
)
def _pr_step(src, dst, ranks, outdeg, num_nodes, method, bin_range, num_bins, block, plan=None):
    """One fused power-iteration step + its L1 movement (the warm-start
    convergence signal ``pagerank_incremental`` polls per round)."""
    from repro.core.executor import execute_reduce

    n = num_nodes
    contrib = ranks / outdeg
    incoming = execute_reduce(
        dst, jnp.take(contrib, src), out_size=n, op="add", method=method,
        bin_range=bin_range, num_bins=num_bins, plan=plan, block=block,
    )
    new = (1.0 - DAMP) / n + DAMP * incoming
    return new, jnp.sum(jnp.abs(new - ranks))


def pagerank_incremental(
    coo: COO,
    ranks_prev: jnp.ndarray | None = None,
    *,
    tol: float = 1e-6,
    max_iters: int = 200,
    method: str | None = None,
) -> PRResult:
    """PageRank to tolerance by warm-started power iteration — the
    incremental maintenance path after an edge batch (DESIGN.md §15.3).
    The PageRank fixpoint of the NEW graph is unique, so the OLD ranks
    are a valid starting point for ANY batch (inserts and deletes
    alike); a small batch leaves the fixpoint nearby and the iteration
    converges in a handful of rounds instead of the cold-start count.
    ``ranks_prev=None`` is the cold start — the from-scratch side of the
    incremental-vs-rebuild crossover (``benchmarks/fig10_updates.py``).

    Iterates the same fused ``op="add"`` reduce as ``pagerank_fused``
    until the L1 movement drops below ``tol``; ``PRResult.iters`` is the
    number of rounds actually run.
    """
    if tol <= 0:
        raise ValueError(f"tol must be > 0, got {tol}")
    if max_iters < 1:
        raise ValueError(f"max_iters must be >= 1, got {max_iters}")
    ex = get_default_executor()
    n = coo.num_nodes
    d = ex.decide_or_forced(
        method, n, coo.num_edges, jnp.float32, kind="reduce"
    )
    outdeg = jnp.maximum(jnp.bincount(coo.src, length=n), 1).astype(jnp.float32)
    ranks = (
        jnp.full((n,), 1.0 / n, jnp.float32)
        if ranks_prev is None
        else jnp.asarray(ranks_prev, jnp.float32)
    )
    it = 0
    while it < max_iters:
        ranks, delta = _pr_step(
            coo.src, coo.dst, ranks, outdeg, n, d.method, d.bin_range,
            d.num_bins, ex.block, d.plan,
        )
        it += 1
        if float(delta) < tol:
            break
    return PRResult(ranks, it)


@functools.lru_cache(maxsize=32)
def _pr_sharded_fn(
    mesh, axis, num_nodes, n_dev, r, iters, method, block, capacity,
    chunks=1, bin_range=None, plan=None,
):
    from repro.compat import shard_map
    from repro.core.distributed_pb import pipelined_owner_reduce
    from jax.sharding import PartitionSpec as P

    n = num_nodes

    def f(src_l, dst_l, outdeg, ranks0):
        def body(_, state):
            ranks, of = state
            # sentinel-padded edges carry dst == n and are dropped by the
            # exchange; src padding is 0, a safe gather
            contrib = jnp.take(ranks / outdeg, jnp.minimum(src_l, n - 1))
            owned, of_i = pipelined_owner_reduce(
                dst_l, contrib, out_size=n, shard_range=r, n_dev=n_dev,
                axis_name=axis, capacity=capacity, chunks=chunks, op="add",
                method=method, bin_range=bin_range, plan=plan, block=block,
            )
            # re-replicate ranks for the next iteration's gather: the
            # owned slices cross the interconnect once per iteration
            gathered = jax.lax.all_gather(owned, axis, tiled=True)
            return (1.0 - DAMP) / n + DAMP * gathered[:n], of | of_i

        return jax.lax.fori_loop(0, iters, body, (ranks0, jnp.asarray(False)))

    spec = P(axis)
    return jax.jit(
        shard_map(
            f,
            mesh=mesh,
            in_specs=(spec, spec, P(None), P(None)),
            out_specs=(P(None), P()),
            check_vma=False,
        )
    )


def pagerank_sharded(
    coo: COO,
    mesh=None,
    iters: int = 10,
    axis_name: str | None = None,
    method: str | None = None,
    capacity: int | None = None,
    pipeline_chunks: int | None = None,
) -> PRResult:
    """PageRank with the mesh-sharded PB reduction (DESIGN.md §9, §13):
    edges are sharded across devices, each iteration owner-routes
    contributions over the interconnect in ``pipeline_chunks``
    double-buffered pieces (``pipelined_owner_reduce``) and fuses them
    into the owned rank slice, then the slices all_gather back to a
    replicated rank vector. Per-device HBM traffic over the edge stream
    drops with device count; only (contribution tuples + rank slices)
    cross the interconnect. ``mesh=None``/1 device degrades to
    ``pagerank_fused``.

    ``method=None``/"auto" asks ``decide`` at the PER-DEVICE shape
    (owned range, received stream) under the topology-extended cache key
    — the device-local method is never hardcoded (DESIGN.md §8.1 / §9);
    the same decision carries the pipeline depth. ``capacity=None``
    estimates the per-destination segment from owner skew; an overflow
    reruns once at the always-safe chunk length.

    Float summation trees differ per shard (and per chunk at K>1):
    equivalent to the single-device result to tolerance, not bit-exactly.
    """
    from repro.core import distributed_pb as dpb
    from repro.core.distributed_pb import (
        _pad_to_multiple,
        resolve_stream_axis,
        shard_range_for,
    )

    n_dev = 1 if mesh is None else int(mesh.shape[resolve_stream_axis(mesh, axis_name)])
    if mesh is None or n_dev == 1:
        return pagerank_fused(coo, iters=iters, method=method)
    axis = resolve_stream_axis(mesh, axis_name)
    ex = get_default_executor()
    n, m = coo.num_nodes, coo.num_edges
    r = shard_range_for(n, n_dev)
    m_local = -(-max(m, 1) // n_dev)
    cap_total = (
        int(capacity)
        if capacity is not None
        else dpb.estimate_capacity(coo.dst, out_size=n, n_dev=n_dev)
    )
    d = ex.decide_or_forced(
        method, r, n_dev * cap_total, jnp.float32, kind="reduce", op="add",
        mesh_shape=tuple(sorted(mesh.shape.items())),
    )
    entry = ex._last_entry if method in (None, "auto") else None
    k = pipeline_chunks if pipeline_chunks is not None else d.pipeline_chunks
    k, chunk_len = dpb._chunk_layout(m_local, k)
    cap = max(1, min(chunk_len, -(-cap_total // k)))
    outdeg = jnp.maximum(jnp.bincount(coo.src, length=n), 1).astype(jnp.float32)
    src_p = _pad_to_multiple(coo.src, n_dev, 0)
    dst_p = _pad_to_multiple(coo.dst, n_dev, n)
    ranks0 = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    fn = _pr_sharded_fn(
        mesh, axis, n, n_dev, r, iters, d.method, ex.block, cap, k,
        d.bin_range, d.plan,
    )
    ranks, overflow = fn(src_p, dst_p, outdeg, ranks0)
    overflow = cap < chunk_len and bool(overflow)
    if overflow:
        # estimated capacity lost tuples: rerun at the always-safe
        # per-chunk capacity (surfaced on the decision entry)
        cap = chunk_len
        fn = _pr_sharded_fn(
            mesh, axis, n, n_dev, r, iters, d.method, ex.block, cap, k,
            d.bin_range, d.plan,
        )
        ranks, _ = fn(src_p, dst_p, outdeg, ranks0)
    if entry is not None:
        entry.update(
            overflow=overflow, capacity=cap, pipeline_chunks=k,
            capacity_source="overflow-fallback" if overflow else (
                "caller" if capacity is not None else "estimated"
            ),
        )
    return PRResult(ranks, iters)
