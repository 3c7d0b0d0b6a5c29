"""Frontier-driven traversal kernels on the PB executor (DESIGN.md §11).

Every workload the repo served before this module was a whole-stream
reduction: the stream length is the edge count and never changes. The
traversal family — level-synchronous BFS, SSSP relaxation rounds, k-core
peeling — is the opposite regime: each iteration expands only the
*frontier*'s out-edges, so the stream length swings from a handful of
tuples to the whole edge array and back within one run. That is exactly
where cache-aware blocking is hardest ("Making Caches Work for Graph
Analytics"; GraphCage's bin-aware frontier scheduling), and it is served
here with three ingredients:

  expansion — ``_expand_frontiers`` gathers the CSR out-edges of the
      current frontier (on the host, where the frontier is compacted)
      into a **fixed-size** stream: the edge stream is padded to a
      power-of-two bucket (``bucket_len``), so the device's reduce
      programs are keyed on O(log m) shapes instead of one per frontier
      size. Padding slots carry an
      IN-RANGE index and the reduce op's identity value, which makes
      them a no-op for every executor method (the clamp trick
      ``distributed_pb.clamp_for_local_reduce`` established — an
      out-of-range bin id is undefined input for counting binning).

  reduction — each level's relaxation is ONE commutative reduce stream
      through ``PBExecutor.reduce_stream`` (or ``shard_reduce_stream``
      over a mesh): ``min`` for BFS levels and SSSP distances, ``max``
      for deterministic BFS parent selection, ``add`` for k-core degree
      decrements. The executor decides the method per level at the
      bucketed shape (its reduce cache keys bucket ``stream_len``), so a
      short frontier never replays a full-stream decision.

  peeling/driver — the level loop is host-side (frontier sizes are
      data-dependent), synchronizing once per level to compact the next
      frontier. ``method="unbinned"`` bypasses the executor with a raw
      dense scatter — the ``segment_min``-style baseline
      ``benchmarks/fig8_traversal.py`` reports speedups against.

``radii.py`` (the paper's Fig. 2b downstream kernel) is rebuilt on this
BFS, so reordering's downstream payoff is itself measured on a PB
workload. Traffic/roofline counterparts: ``traffic.traversal_bytes``,
``roofline.TraversalRoofline``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.executor import HostStreamStats, PBExecutor, get_default_executor
from repro.core.graph import CSR

_INT_MAX = np.iinfo(np.int32).max
_INT_MIN = np.iinfo(np.int32).min
_F32_MAX = float(np.finfo(np.float32).max)

# Methods the per-level reduction accepts: the executor's reduce set
# plus the unbinned dense-scatter baseline.
TRAVERSAL_METHODS = (
    "auto", "sort", "counting", "pallas", "hierarchical", "fused", "unbinned",
)

# The subset a BATCHED traversal may force: one decision + one vmapped
# program covers every query lane (``PBExecutor.reduce_streams``), so
# only the vmap-able reduce methods (plus the unbinned baseline) apply.
# ``auto`` still consults ``decide`` and batch-clamps if needed.
BATCHED_TRAVERSAL_METHODS = ("auto", "sort", "counting", "fused", "unbinned")


def bucket_len(n: int, minimum: int = 256) -> int:
    """Next power-of-two at least ``minimum``: the static stream length a
    frontier of ``n`` tuples is padded to. Bounds distinct jit shapes per
    run at O(log m) while wasting < 2x work on the padded tail."""
    b = minimum
    while b < n:
        b *= 2
    return b


class _Edges(NamedTuple):
    """One level's frontier out-edges, (B, bucket) host arrays (one lane
    per frontier): destination vertex, the edge's CSR slot (for weight
    gathers), the owning frontier vertex, and the validity mask. Padding
    slots point at CSR slot 0 — an in-range index — and callers give
    them the reduce op's identity value via ``ok``."""

    nbr: np.ndarray
    pos: np.ndarray
    src: np.ndarray
    ok: np.ndarray


def _expand_frontiers(
    stats: HostStreamStats, offs_host, neighs_host, fronts, bucket_edges: int
) -> _Edges:
    """Gather the CSR out-edges of each host frontier into one row of
    fixed-size ``bucket_edges`` arrays.

    The expansion is host numpy: the frontiers are compacted on the
    host anyway, a frontier's edge slots are runs of consecutive CSR
    positions (``np.repeat`` of the run starts), and on a TPU the device
    rendering of the same expansion (a searchsorted over the frontier's
    degree prefix plus gathers) compiled a new program per bucket shape
    in tens of seconds (DESIGN.md §11.1). Only the reduce runs on the
    device, on the streams built here."""
    with stats.timed():
        B = len(fronts)
        pos = np.zeros((B, bucket_edges), np.int32)
        src = np.zeros((B, bucket_edges), np.int32)
        ok = np.zeros((B, bucket_edges), bool)
        for q, f in enumerate(fronts):
            starts = offs_host[f]
            deg = offs_host[f + 1] - starts
            total = int(deg.sum())
            # slot j of frontier vertex k sits at starts[k] + (j - excl[k])
            pos[q, :total] = np.arange(total, dtype=np.int32) + np.repeat(
                starts - (np.cumsum(deg) - deg), deg
            )
            src[q, :total] = np.repeat(f, deg)
            ok[q, :total] = True
        spans.count("levels")
        return _Edges(neighs_host[pos], pos, src, ok)


def _seeded(stats: HostStreamStats, srcs, n: int, hot, cold, dtype) -> jnp.ndarray:
    """(B, n) device array: ``hot`` at ``(q, srcs[q])`` (``hot`` a scalar
    or one value per lane), ``cold`` elsewhere. Built on the host: a
    device scatter would compile one program per (B, n)."""
    with stats.timed():
        srcs = np.atleast_1d(np.asarray(srcs))
        a = np.full((srcs.size, n), cold, dtype)
        a[np.arange(srcs.size), srcs] = hot
    return stats.upload(a)


@jax.jit
def _improve(cand, dist):
    """``(cand < dist, min(cand, dist))``: the relaxation's new frontier
    mask and distances, one program per (B, n)."""
    better = cand < dist
    return better, jnp.where(better, cand, dist)


class TraversalResult(NamedTuple):
    """One frontier traversal: distances/labels + how it ran."""

    dist: jnp.ndarray  # (n,) levels (BFS, int32) or distances (SSSP, f32)
    parent: Optional[jnp.ndarray]  # (n,) BFS tree parent (-1 = unreached)
    levels: int  # expansion rounds executed
    converged: bool  # frontier drained before max_iters
    frontier_sizes: Tuple[int, ...]  # vertices per level, level 0 first
    level_edges: Tuple[int, ...]  # real (unpadded) tuples expanded per level
    decisions: Tuple[dict, ...]  # executor decisions, annotated with "level"


class KCoreResult(NamedTuple):
    """k-core peeling: surviving vertices + peel trajectory."""

    in_core: jnp.ndarray  # (n,) bool — member of the k-core
    rounds: int
    converged: bool
    removed_per_round: Tuple[int, ...]
    decisions: Tuple[dict, ...]


class _LevelReducer:
    """Routes one level's (idx, val) stream to the chosen reduction path
    and collects the executor's decisions, tagged with the level."""

    def __init__(self, ex: PBExecutor, method, mesh, axis_name):
        self.ex = ex
        self.method = None if method in (None, "auto") else method
        self.mesh = mesh
        self.axis_name = axis_name
        self.decisions: list = []
        self._level = 0

    def set_level(self, level: int) -> None:
        self._level = level

    def __call__(self, idx, val, *, out_size: int, op: str):
        if self.method == "unbinned":
            # the segment_min-style baseline: one raw dense scatter, no
            # binning — what fig8 measures PB speedups against. The
            # reference scatter-reduce IS that semantics; one definition
            # keeps the baseline and the test oracle from diverging.
            from repro.kernels.ref import scatter_reduce_ref

            return scatter_reduce_ref(idx, val, out_size, op=op)
        sink: list = []
        self.ex.add_decision_sink(sink)
        try:
            if self.mesh is not None:
                out = self.ex.shard_reduce_stream(
                    idx, val, out_size=out_size, mesh=self.mesh, op=op,
                    axis_name=self.axis_name, method=self.method,
                )
            else:
                out = self.ex.reduce_stream(
                    idx, val, out_size=out_size, op=op, method=self.method
                )
        finally:
            self.ex.remove_decision_sink(sink)
        for e in sink:
            self.decisions.append({**e, "level": self._level})
        return out

    def batched(self, idx, val, *, out_size: int, op: str):
        """One level of MANY query lanes: (B, m) streams reduced under a
        single decision through ``PBExecutor.reduce_streams`` — the
        micro-batch coalescing the serving frontend rides (DESIGN.md
        §12). ``unbinned`` vmaps the raw dense scatter, keeping the
        baseline semantics identical per lane."""
        if self.method == "unbinned":
            from repro.kernels.ref import scatter_reduce_ref

            return jax.vmap(
                lambda i, v: scatter_reduce_ref(i, v, out_size, op=op)
            )(idx, val)
        sink: list = []
        self.ex.add_decision_sink(sink)
        try:
            out = self.ex.reduce_streams(
                idx, val, out_size=out_size, op=op, method=self.method
            )
        finally:
            self.ex.remove_decision_sink(sink)
        for e in sink:
            self.decisions.append({**e, "level": self._level})
        return out


def _resolve(method: str):
    if method not in TRAVERSAL_METHODS:
        raise ValueError(
            f"unknown traversal method: {method!r} "
            f"(want one of {TRAVERSAL_METHODS})"
        )


def _resolve_batched(method: str):
    if method not in BATCHED_TRAVERSAL_METHODS:
        raise ValueError(
            f"unknown batched traversal method: {method!r} "
            f"(want one of {BATCHED_TRAVERSAL_METHODS})"
        )


def _masked(
    stats: HostStreamStats, ok: np.ndarray, real: np.ndarray, identity, dtype
) -> jnp.ndarray:
    """A level's reduce values on the device: ``real`` at valid slots,
    the op's ``identity`` at padding."""
    with stats.timed():
        a = np.where(ok, real, identity).astype(dtype)
    return stats.upload(a)


def _relax_values(
    stats: HostStreamStats, e: _Edges, dist_host: np.ndarray, w_host: np.ndarray
) -> jnp.ndarray:
    """SSSP reduce values ``dist[u] + w(u, v)`` (float32, as the device
    adds them) for (B, n) host distances; ``float32 max`` at padding."""
    with stats.timed():
        val = np.full(e.ok.shape, _F32_MAX, np.float32)
        d = np.take_along_axis(dist_host, e.src, axis=1)
        val[e.ok] = d[e.ok] + w_host[e.pos[e.ok]]
    return stats.upload(val)


def bfs(
    csr: CSR,
    source: int,
    *,
    executor: Optional[PBExecutor] = None,
    method: str = "auto",
    mesh=None,
    axis_name: Optional[str] = None,
    max_iters: Optional[int] = None,
    with_parents: bool = True,
) -> TraversalResult:
    """Level-synchronous BFS: each level is one ``op="min"`` reduce of
    (neighbor, level+1) tuples over the frontier's out-edges, plus — when
    ``with_parents`` — one ``op="max"`` reduce of (neighbor, frontier
    vertex) tuples that picks a deterministic BFS-tree parent (the
    largest-id predecessor), method-independently.

    ``dist[v]`` is the BFS level (``INT32_MAX`` when unreached). A mesh
    routes every per-level reduction through ``shard_reduce_stream``.
    """
    _resolve(method)
    ex = executor or get_default_executor()
    hs = ex.host_streams
    n = csr.num_nodes
    if not 0 <= source < n:
        raise ValueError(f"source {source} outside [0, {n})")
    max_iters = n if max_iters is None else max_iters
    offs_host, neighs_host = np.asarray(csr.offsets), np.asarray(csr.neighs)
    red = _LevelReducer(ex, method, mesh, axis_name)

    dist = _seeded(hs, source, n, 0, _INT_MAX, np.int32)[0]
    parent = _seeded(hs, source, n, source, -1, np.int32)[0] if with_parents else None
    frontier = np.asarray([source], np.int32)
    sizes = [1]
    edges = []
    level = 0
    while frontier.size and level < max_iters:
        red.set_level(level)
        total = int((offs_host[frontier + 1] - offs_host[frontier]).sum())
        edges.append(total)
        if total == 0:
            # the frontier has no out-edges: the round ran (levels and
            # radii's iters count it, matching the pre-§11 dense BFS)
            # but expanded nothing — 0 in level_edges, trailing 0 in
            # frontier_sizes, no reduce
            level += 1
            frontier = np.zeros(0, np.int32)
            sizes.append(0)
            break
        e = _expand_frontiers(hs, offs_host, neighs_host, [frontier], bucket_len(total))
        nbr = hs.upload(e.nbr[0])
        val = _masked(hs, e.ok[0], level + 1, _INT_MAX, np.int32)
        cand = red(nbr, val, out_size=n, op="min")
        newly = cand < dist
        if with_parents:
            pval = _masked(hs, e.ok[0], e.src[0], _INT_MIN, np.int32)
            pmax = red(nbr, pval, out_size=n, op="max")
            parent = jnp.where(newly, pmax, parent)
        dist = jnp.where(newly, cand, dist)
        frontier = np.flatnonzero(np.asarray(newly)).astype(np.int32)
        sizes.append(int(frontier.size))
        level += 1
    return TraversalResult(
        dist=dist,
        parent=parent,
        levels=level,
        converged=frontier.size == 0,
        frontier_sizes=tuple(sizes),
        level_edges=tuple(edges),
        decisions=tuple(red.decisions),
    )


def sssp(
    csr: CSR,
    weights: jnp.ndarray,
    source: int,
    *,
    executor: Optional[PBExecutor] = None,
    method: str = "auto",
    mesh=None,
    axis_name: Optional[str] = None,
    max_iters: Optional[int] = None,
) -> TraversalResult:
    """Frontier-driven SSSP (delta-stepping-style rounds): each round
    relaxes the out-edges of every vertex whose distance improved last
    round — one ``op="min"`` reduce of (neighbor, dist[u] + w(u,v))
    tuples. With non-negative weights this converges in at most n rounds
    (Bellman-Ford bound); the frontier restriction makes the common case
    far cheaper, exactly like BFS.

    ``weights`` is aligned with ``csr.neighs`` (one weight per CSR edge
    slot). ``dist`` is float32 with ``float32 max`` at unreached
    vertices (not ``inf``: the executor's min identity).
    """
    _resolve(method)
    ex = executor or get_default_executor()
    hs = ex.host_streams
    n = csr.num_nodes
    if not 0 <= source < n:
        raise ValueError(f"source {source} outside [0, {n})")
    if weights.shape[0] != csr.num_edges:
        raise ValueError(
            f"weights must align with csr.neighs: {weights.shape[0]} != "
            f"{csr.num_edges}"
        )
    w_host = np.asarray(weights, np.float32)
    max_iters = n if max_iters is None else max_iters
    offs_host, neighs_host = np.asarray(csr.offsets), np.asarray(csr.neighs)
    red = _LevelReducer(ex, method, mesh, axis_name)

    dist = _seeded(hs, source, n, 0.0, _F32_MAX, np.float32)[0]
    frontier = np.asarray([source], np.int32)
    sizes = [1]
    edges = []
    rounds = 0
    while frontier.size and rounds < max_iters:
        red.set_level(rounds)
        total = int((offs_host[frontier + 1] - offs_host[frontier]).sum())
        edges.append(total)
        if total == 0:  # same trace semantics as the bfs zero-edge exit
            rounds += 1
            frontier = np.zeros(0, np.int32)
            sizes.append(0)
            break
        e = _expand_frontiers(hs, offs_host, neighs_host, [frontier], bucket_len(total))
        val = _relax_values(hs, e, np.asarray(dist)[None], w_host)[0]
        cand = red(hs.upload(e.nbr[0]), val, out_size=n, op="min")
        improved = cand < dist
        dist = jnp.where(improved, cand, dist)
        frontier = np.flatnonzero(np.asarray(improved)).astype(np.int32)
        sizes.append(int(frontier.size))
        rounds += 1
    return TraversalResult(
        dist=dist,
        parent=None,
        levels=rounds,
        converged=frontier.size == 0,
        frontier_sizes=tuple(sizes),
        level_edges=tuple(edges),
        decisions=tuple(red.decisions),
    )


def k_core(
    csr: CSR,
    k: int,
    *,
    executor: Optional[PBExecutor] = None,
    method: str = "auto",
    mesh=None,
    axis_name: Optional[str] = None,
    max_iters: Optional[int] = None,
) -> KCoreResult:
    """k-core peeling: iteratively remove vertices of degree < k; each
    peel round streams the removed vertices' out-edges through one
    ``op="add"`` reduce of (neighbor, 1) tuples — the degree decrement.

    Degree here is the CSR out-degree and removal deletes the removed
    vertex's out-edges (on a symmetrized graph this is the textbook
    k-core; on a directed CSR it is the out-degree core). Decrements
    onto already-removed neighbors are harmless — their membership is
    final.
    """
    _resolve(method)
    if k < 0:
        raise ValueError(f"k must be >= 0, got {k}")
    ex = executor or get_default_executor()
    hs = ex.host_streams
    n = csr.num_nodes
    max_iters = n if max_iters is None else max_iters
    offs_host, neighs_host = np.asarray(csr.offsets), np.asarray(csr.neighs)
    red = _LevelReducer(ex, method, mesh, axis_name)

    deg = (csr.offsets[1:] - csr.offsets[:-1]).astype(jnp.int32)
    alive = np.ones(n, bool)  # host-side: the frontier is compacted there
    frontier = np.flatnonzero(np.asarray(deg) < k).astype(np.int32)
    removed = [int(frontier.size)] if frontier.size else []
    rounds = 0
    while frontier.size and rounds < max_iters:
        red.set_level(rounds)
        alive[frontier] = False
        total = int((offs_host[frontier + 1] - offs_host[frontier]).sum())
        if total:
            e = _expand_frontiers(hs, offs_host, neighs_host, [frontier], bucket_len(total))
            ones = _masked(hs, e.ok[0], 1, 0, np.int32)
            deg = deg - red(hs.upload(e.nbr[0]), ones, out_size=n, op="add")
        frontier = np.flatnonzero(alive & (np.asarray(deg) < k)).astype(np.int32)
        if frontier.size:
            removed.append(int(frontier.size))
        rounds += 1
    return KCoreResult(
        in_core=jnp.asarray(alive),
        rounds=rounds,
        converged=frontier.size == 0,
        removed_per_round=tuple(removed),
        decisions=tuple(red.decisions),
    )


def bfs_incremental(
    csr: CSR,
    source: int,
    dist_prev: jnp.ndarray,
    touched,
    *,
    has_deletes: bool = False,
    executor: Optional[PBExecutor] = None,
    method: str = "auto",
    max_iters: Optional[int] = None,
) -> Tuple[TraversalResult, str]:
    """BFS after an edge batch, re-relaxing only the batch-touched
    frontier (DESIGN.md §15.3). Edge INSERTS can only shorten BFS
    distances, so the pre-batch ``dist_prev`` is a valid upper bound:
    seed the frontier with the reached batch endpoints and run the same
    per-level ``op="min"`` relaxation as ``bfs`` until it drains —
    typically O(batch) work instead of O(m). Deletions can lengthen
    distances, which monotone relaxation cannot express, so
    ``has_deletes=True`` falls back to a from-scratch ``bfs``.

    ``csr`` is the POST-batch graph; ``touched`` the batch's endpoint
    vertices (``updates.touched_vertices``). Returns ``(result, mode)``
    with ``mode`` one of ``"incremental"``/``"full"``; the incremental
    result carries ``parent=None`` (levels/edges count only the
    re-relaxation rounds).
    """
    _resolve(method)
    ex = executor or get_default_executor()
    hs = ex.host_streams
    n = csr.num_nodes
    if not 0 <= source < n:
        raise ValueError(f"source {source} outside [0, {n})")
    if has_deletes:
        return (
            bfs(
                csr, source, executor=ex, method=method,
                max_iters=max_iters, with_parents=False,
            ),
            "full",
        )
    max_iters = n if max_iters is None else max_iters
    offs_host, neighs_host = np.asarray(csr.offsets), np.asarray(csr.neighs)
    red = _LevelReducer(ex, method, None, None)

    dist = jnp.asarray(dist_prev, jnp.int32)
    dist_host = np.asarray(dist)
    touched_np = np.unique(np.asarray(touched, np.int32))
    # only reached endpoints can propagate a shorter level
    frontier = touched_np[dist_host[touched_np] < _INT_MAX]
    sizes = [int(frontier.size)]
    edges = []
    rounds = 0
    while frontier.size and rounds < max_iters:
        red.set_level(rounds)
        total = int((offs_host[frontier + 1] - offs_host[frontier]).sum())
        edges.append(total)
        if total == 0:  # same trace semantics as the bfs zero-edge exit
            rounds += 1
            frontier = np.zeros(0, np.int32)
            sizes.append(0)
            break
        e = _expand_frontiers(hs, offs_host, neighs_host, [frontier], bucket_len(total))
        # frontier vertices sit at heterogeneous levels after a batch,
        # so relax dist[u] + 1 (unit-weight sssp) rather than level + 1
        val = _masked(hs, e.ok[0], dist_host[e.src[0]] + 1, _INT_MAX, np.int32)
        cand = red(hs.upload(e.nbr[0]), val, out_size=n, op="min")
        improved = cand < dist
        dist = jnp.where(improved, cand, dist)
        dist_host = np.asarray(dist)
        frontier = np.flatnonzero(np.asarray(improved)).astype(np.int32)
        sizes.append(int(frontier.size))
        rounds += 1
    return (
        TraversalResult(
            dist=dist,
            parent=None,
            levels=rounds,
            converged=frontier.size == 0,
            frontier_sizes=tuple(sizes),
            level_edges=tuple(edges),
            decisions=tuple(red.decisions),
        ),
        "incremental",
    )


# ---------------------------------------------------------------------------
# Micro-batched traversal: many source-vertex queries per reduce call.
# ---------------------------------------------------------------------------


def bfs_batched(
    csr: CSR,
    sources,
    *,
    executor: Optional[PBExecutor] = None,
    method: str = "auto",
    max_iters: Optional[int] = None,
    with_parents: bool = False,
) -> TraversalResult:
    """Level-synchronous BFS from MANY sources at once: each level is ONE
    batched reduce over (B, bucket) per-query streams
    (``PBExecutor.reduce_streams`` — one decision, one vmapped program
    for the whole batch). Lane q computes exactly what ``bfs(csr,
    sources[q])`` computes: the integer ``min``/``max`` relaxations are
    order-free, and a lane whose frontier drained streams only identity
    values, so its distances are final. This is the micro-batch
    coalescing path the serving frontend ticks on (DESIGN.md §12).

    Returns a ``TraversalResult`` whose ``dist`` (and ``parent``) carry a
    leading batch axis; ``frontier_sizes``/``level_edges`` aggregate over
    the batch.
    """
    _resolve_batched(method)
    ex = executor or get_default_executor()
    hs = ex.host_streams
    n = csr.num_nodes
    srcs = np.atleast_1d(np.asarray(sources, np.int32))
    if srcs.size == 0:
        raise ValueError("bfs_batched needs at least one source")
    if not ((srcs >= 0) & (srcs < n)).all():
        raise ValueError(f"sources outside [0, {n}): {srcs}")
    B = srcs.size
    max_iters = n if max_iters is None else max_iters
    offs_host, neighs_host = np.asarray(csr.offsets), np.asarray(csr.neighs)
    red = _LevelReducer(ex, method, None, None)

    dist = _seeded(hs, srcs, n, 0, _INT_MAX, np.int32)
    parent = _seeded(hs, srcs, n, srcs, -1, np.int32) if with_parents else None
    fronts = [np.asarray([s], np.int32) for s in srcs]
    sizes = [B]
    edges = []
    level = 0
    while any(f.size for f in fronts) and level < max_iters:
        red.set_level(level)
        per_q = [
            int((offs_host[f + 1] - offs_host[f]).sum()) if f.size else 0
            for f in fronts
        ]
        total = sum(per_q)
        edges.append(total)
        if total == 0:  # no lane expands: same trace semantics as bfs
            level += 1
            fronts = [np.zeros(0, np.int32) for _ in fronts]
            sizes.append(0)
            break
        e = _expand_frontiers(hs, offs_host, neighs_host, fronts, bucket_len(max(per_q)))
        nbr = hs.upload(e.nbr)
        val = _masked(hs, e.ok, level + 1, _INT_MAX, np.int32)
        cand = red.batched(nbr, val, out_size=n, op="min")
        newly, dist = _improve(cand, dist)
        if with_parents:
            pval = _masked(hs, e.ok, e.src, _INT_MIN, np.int32)
            pmax = red.batched(nbr, pval, out_size=n, op="max")
            parent = jnp.where(newly, pmax, parent)
        newly_np = np.asarray(newly)
        fronts = [np.flatnonzero(newly_np[q]).astype(np.int32) for q in range(B)]
        sizes.append(int(sum(f.size for f in fronts)))
        level += 1
    return TraversalResult(
        dist=dist,
        parent=parent,
        levels=level,
        converged=not any(f.size for f in fronts),
        frontier_sizes=tuple(sizes),
        level_edges=tuple(edges),
        decisions=tuple(red.decisions),
    )


def sssp_batched(
    csr: CSR,
    weights: jnp.ndarray,
    sources,
    *,
    executor: Optional[PBExecutor] = None,
    method: str = "auto",
    max_iters: Optional[int] = None,
) -> TraversalResult:
    """Frontier-driven SSSP from MANY sources: the batched analogue of
    ``sssp`` (see ``bfs_batched`` for the coalescing contract). ``min``
    over float32 is order-free, so lane q is bit-for-bit ``sssp(csr,
    weights, sources[q])`` under the same reduce method."""
    _resolve_batched(method)
    ex = executor or get_default_executor()
    hs = ex.host_streams
    n = csr.num_nodes
    if weights.shape[0] != csr.num_edges:
        raise ValueError(
            f"weights must align with csr.neighs: {weights.shape[0]} != "
            f"{csr.num_edges}"
        )
    srcs = np.atleast_1d(np.asarray(sources, np.int32))
    if srcs.size == 0:
        raise ValueError("sssp_batched needs at least one source")
    if not ((srcs >= 0) & (srcs < n)).all():
        raise ValueError(f"sources outside [0, {n}): {srcs}")
    B = srcs.size
    w_host = np.asarray(weights, np.float32)
    max_iters = n if max_iters is None else max_iters
    offs_host, neighs_host = np.asarray(csr.offsets), np.asarray(csr.neighs)
    red = _LevelReducer(ex, method, None, None)

    dist = _seeded(hs, srcs, n, 0.0, _F32_MAX, np.float32)
    fronts = [np.asarray([s], np.int32) for s in srcs]
    sizes = [B]
    edges = []
    rounds = 0
    while any(f.size for f in fronts) and rounds < max_iters:
        red.set_level(rounds)
        per_q = [
            int((offs_host[f + 1] - offs_host[f]).sum()) if f.size else 0
            for f in fronts
        ]
        total = sum(per_q)
        edges.append(total)
        if total == 0:
            rounds += 1
            fronts = [np.zeros(0, np.int32) for _ in fronts]
            sizes.append(0)
            break
        e = _expand_frontiers(hs, offs_host, neighs_host, fronts, bucket_len(max(per_q)))
        val = _relax_values(hs, e, np.asarray(dist), w_host)
        cand = red.batched(hs.upload(e.nbr), val, out_size=n, op="min")
        improved, dist = _improve(cand, dist)
        improved_np = np.asarray(improved)
        fronts = [
            np.flatnonzero(improved_np[q]).astype(np.int32) for q in range(B)
        ]
        sizes.append(int(sum(f.size for f in fronts)))
        rounds += 1
    return TraversalResult(
        dist=dist,
        parent=None,
        levels=rounds,
        converged=not any(f.size for f in fronts),
        frontier_sizes=tuple(sizes),
        level_edges=tuple(edges),
        decisions=tuple(red.decisions),
    )


# ---------------------------------------------------------------------------
# Personalized PageRank: restart mass as an op=add reduce stream.
# ---------------------------------------------------------------------------


@jax.jit
def _ppr_contrib(ranks, outdeg, src):
    """Each edge's (m, B) contribution ``ranks[u] / outdeg[u]``."""
    return jnp.take(ranks / outdeg[:, None], src, axis=0)


@jax.jit
def _ppr_restart(restart, incoming, damp):
    return (1.0 - damp) * restart + damp * incoming


class PPRResult(NamedTuple):
    """Personalized PageRank: ranks + how the reductions ran."""

    ranks: jnp.ndarray  # (n,) single query / (B, n) batched
    iters: int
    decisions: Tuple[dict, ...]  # executor decisions, tagged with "level"


def personalized_pagerank(
    csr: CSR,
    sources=None,
    *,
    iters: int = 20,
    damp: float = 0.85,
    executor: Optional[PBExecutor] = None,
    method: str = "auto",
) -> PPRResult:
    """Personalized PageRank by power iteration over the CSR edge stream:
    every iteration is ONE commutative ``op="add"`` reduce of (neighbor,
    contribution) tuples — the same stream ``pagerank_fused`` pushes —
    with the restart mass re-injected at the source instead of uniformly:

        ranks <- (1 - damp) * e_source + damp * A^T (ranks / outdeg)

    ``sources=None`` is the uniform restart (global PageRank on a CSR);
    a scalar personalizes to one vertex; an array of B sources runs B
    queries through ONE batched reduce per iteration — contributions for
    all queries ride the SAME index stream as an (m, B) value block, so
    the index traffic (and the executor decision) is paid once per
    iteration for the whole batch. That is the serving frontend's
    coalesced PPR tick (DESIGN.md §12). Dangling vertices follow the
    repo-wide PageRank semantics (out-degree clamped to 1: their mass is
    dropped, not redistributed), so results are comparable with
    ``pagerank_*`` and the numpy oracle below.
    """
    _resolve(method)
    if method in ("pallas", "hierarchical"):
        # (m, B) value blocks: reduce_stream would clamp pallas to sort
        # anyway; reject up front so forced methods mean what they say
        raise ValueError(
            f"personalized_pagerank supports methods "
            f"{('auto', 'sort', 'counting', 'fused', 'unbinned')}, got {method!r}"
        )
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    ex = executor or get_default_executor()
    hs = ex.host_streams
    n = csr.num_nodes
    deg_host = np.diff(np.asarray(csr.offsets))
    # edge -> owning row, on the host (the CSR is sorted by row)
    with hs.timed():
        owner = np.repeat(np.arange(n, dtype=np.int32), deg_host)
    src = hs.upload(owner)
    dst = csr.neighs
    outdeg = jnp.asarray(np.maximum(deg_host, 1).astype(np.float32))

    single = sources is None or np.ndim(sources) == 0
    if sources is None:
        restart = jnp.full((n, 1), 1.0 / n, jnp.float32)
    else:
        srcs = np.atleast_1d(np.asarray(sources, np.int32))
        if srcs.size == 0:
            raise ValueError("personalized_pagerank needs >= 1 source")
        if not ((srcs >= 0) & (srcs < n)).all():
            raise ValueError(f"sources outside [0, {n}): {srcs}")
        restart = _seeded(hs, srcs, n, 1.0, 0.0, np.float32).T
    red = _LevelReducer(ex, method, None, None)
    ranks = restart
    for it in range(iters):
        red.set_level(it)
        incoming = red(dst, _ppr_contrib(ranks, outdeg, src), out_size=n, op="add")
        ranks = _ppr_restart(restart, incoming, damp)
    out = ranks[:, 0] if single else ranks.T
    return PPRResult(ranks=out, iters=iters, decisions=tuple(red.decisions))


# ---------------------------------------------------------------------------
# Oracles (numpy, tests/benchmarks only).
# ---------------------------------------------------------------------------


def personalized_pagerank_oracle(
    csr: CSR, source=None, iters: int = 20, damp: float = 0.85
) -> np.ndarray:
    """float64 power iteration with the same semantics as
    ``personalized_pagerank`` (clamped out-degree, dropped dangling
    mass) — the allclose target for the serving tests."""
    off, nei = np.asarray(csr.offsets), np.asarray(csr.neighs)
    n = csr.num_nodes
    src = np.repeat(np.arange(n), np.diff(off))
    outdeg = np.maximum(np.diff(off), 1).astype(np.float64)
    if source is None:
        restart = np.full(n, 1.0 / n)
    else:
        restart = np.zeros(n)
        restart[int(source)] = 1.0
    ranks = restart.copy()
    for _ in range(iters):
        contrib = ranks / outdeg
        incoming = np.zeros(n)
        np.add.at(incoming, nei, contrib[src])
        ranks = (1.0 - damp) * restart + damp * incoming
    return ranks


def k_core_oracle(csr: CSR, k: int) -> np.ndarray:
    """Sequential peeling with the same semantics as ``k_core``."""
    off, nei = np.asarray(csr.offsets), np.asarray(csr.neighs)
    n = csr.num_nodes
    deg = np.diff(off).astype(np.int64)
    alive = np.ones(n, bool)
    frontier = np.flatnonzero(deg < k)
    while frontier.size:
        alive[frontier] = False
        for u in frontier:
            for v in nei[off[u] : off[u + 1]]:
                deg[v] -= 1
        frontier = np.flatnonzero(alive & (deg < k))
    return alive
