"""PBExecutor — the single entry point for every irregular-update stream.

The paper's thesis is that Propagation Blocking is *one* optimization
that serves graph processing (PageRank §5.2, Components), pre-processing
(Neighbor-Populate, Algorithm 2) and — in this repo's extension — the
LM-framework streams (MoE dispatch, embedding gradients) alike. Before
this module, every consumer hand-picked its own binning path; now they
all register a *stream* and the executor picks the *method*:

  ``sort``          — XLA stable sort by bin id (``pb.binning_sort``),
                      the semantic reference. Best for short streams
                      where sort latency dominates (paper §3's software
                      PB at small inputs).
  ``counting``      — blockwise counting sort with per-bin VMEM cursors
                      (``pb.binning_counting``) — Algorithm 2's Binning
                      phase, one bin range per pass.
  ``pallas``        — the same algorithm as the Pallas TPU kernels
                      (``kernels.binning.counting_positions``): histogram
                      + positions + scatter. 1-D single-array values only.
  ``hierarchical``  — multi-pass COBRA (``core.cobra``), the §4 knob-free
                      execution driven by a ``CobraPlan``: used when one
                      pass's C-Buffer fan-out would exceed the fast level.
  ``fused``         — (``reduce_stream`` only) single-sweep
                      bin-and-accumulate (``kernels/fused.py``): C-Buffer
                      flushes reduce into a VMEM-resident accumulator, so
                      the binned stream never exists in HBM. Legal for
                      commutative reductions whose accumulator fits the
                      fast level (DESIGN.md §8).

Selection is plan-driven (``HardwareModel`` capacities, paper §3's two
optima) with an optional **measured autotuner**: timings are cached per
``(num_indices, stream_len, dtype, backend)`` key, persisted under
``~/.cache/repro_pb/`` (override with ``REPRO_PB_CACHE_DIR``), with an
in-repo fallback table for cold starts on read-only filesystems. The
full decision tree is documented in DESIGN.md §3.

A ``vmap``-able batched path (``bin_streams`` / ``scatter_add_batched``)
serves many-small-frontier traffic: one decision covers the whole batch,
amortizing planning the way serving-style workloads need.

At mesh scale, ``shard_reduce_stream`` adds the device level of the
C-Buffer hierarchy (``core/distributed_pb.py``, DESIGN.md §9): the
coarsest binning pass owner-routes tuples over the interconnect, then
each device runs the decision-driven local reduce over its owned index
range. Cache keys carry the device topology, so a decision measured on
one mesh is never replayed on another.

Extending with a new workload = expressing it as an (indices, values)
stream and calling this module — see DESIGN.md §4.
"""
from __future__ import annotations

import contextlib
import functools
import json
import math
import os
import time
from dataclasses import dataclass, replace as _dc_replace
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pb, spans
from repro.core.cobra import hierarchical_binning
from repro.core.plan import (
    FUSED_BIN_RANGE,
    FUSED_BLOCK,
    FUSED_CAP,
    CobraPlan,
    HardwareModel,
    binning_optimal_num_bins,
    compromise_bin_range,
    fused_fits,
    fused_vmem_bytes,
    num_bins_for_range,
)
from repro.kernels import resolve_interpret

METHODS = ("sort", "counting", "pallas", "hierarchical")

# Reduction entry point (``reduce_stream``): the four binning methods
# run two-phase (bin, then Bin-Read reduce); ``fused`` is the
# single-sweep bin-and-accumulate that never materializes the binned
# stream in HBM (kernels/fused.py, DESIGN.md §8).
REDUCE_METHODS = METHODS + ("fused",)

# Commutative reductions the fused path may legally absorb on chip.
# Anything else (neighbor placement, capacity-clipped dispatch, ...)
# is order-sensitive and must keep the two-phase ``bin_stream`` path.
# ``min``/``max`` serve the frontier relaxations (SSSP, BFS parent
# selection — core/traversal.py) and label propagation.
REDUCE_OPS = ("add", "min", "max")

# Below this stream length XLA's stable sort is latency-, not
# bandwidth-bound, and always wins (DESIGN.md §3.1).
_SORT_THRESHOLD = 4096

# decision_log is a bounded trace for BENCH_smoke.json, not an audit
# trail: long-running consumers (training loops) must not leak memory.
_DECISION_LOG_CAP = 512


# ---------------------------------------------------------------------------
# Functional core: jit-friendly, method chosen statically.
# ---------------------------------------------------------------------------


def execute_binning(
    indices: jnp.ndarray,
    values,
    *,
    bin_range: int,
    num_bins: int,
    method: str = "sort",
    plan: Optional[CobraPlan] = None,
    block: int = 2048,
    interpret: Optional[bool] = None,
) -> pb.Bins:
    """Bin one (indices, values) stream with the given method.

    This is the executor's traceable core (callers may jit around it;
    ``method``/``bin_range``/``num_bins`` are static). Every method is a
    stable partition by ``indices // bin_range``, so all four agree with
    ``kernels.ref.binned_stream_ref`` — the invariant that keeps
    non-commutative consumers (paper §2) correct under method swaps.

    ``interpret=None`` resolves per backend (interpret-mode Pallas off
    TPU, compiled Mosaic on TPU; ``repro.kernels.resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    if method not in METHODS:
        raise ValueError(f"unknown binning method: {method!r} (want one of {METHODS})")
    m = indices.shape[0]
    if m == 0:  # empty frontier: nothing to route
        nb = plan.num_bins if (method == "hierarchical" and plan) else num_bins
        return pb.Bins(
            idx=indices,
            val=values,
            starts=jnp.zeros((nb + 1,), jnp.int32),
            bin_range=bin_range,
        )
    if method == "sort":
        return pb.binning_sort(indices, values, bin_range, num_bins)
    if method == "counting":
        return pb.binning_counting(indices, values, bin_range, num_bins, block=block)
    if method == "pallas":
        if not (isinstance(values, jnp.ndarray) and values.ndim == 1):
            raise ValueError("pallas binning supports a single 1-D value array")
        from repro.kernels import ops  # deferred: kernels import pallas

        return ops.pb_binning(
            indices,
            values,
            bin_range=bin_range,
            num_bins=num_bins,
            block=min(block, 1024),
            interpret=interpret,
        )
    # hierarchical
    if plan is None:
        raise ValueError("hierarchical binning needs a CobraPlan")
    return hierarchical_binning(indices, values, plan, method="counting", block=block)


# ---------------------------------------------------------------------------
# Fused single-sweep reduction (DESIGN.md §8).
# ---------------------------------------------------------------------------


def fused_realization(
    out_size: int,
    values_shape: Tuple[int, ...],
    dtype,
    *,
    interpret: bool,
    use_pallas: bool = False,
    bin_range: Optional[int] = None,
    f_tile: Optional[int] = None,
    vmem_budget: Optional[int] = None,
) -> str:
    """Which realization a ``fused`` reduce of this shape runs; the
    decision-log entry of every ``fused`` decision names it.

    ``"pallas"`` — the C-Buffer kernel — when it is compiled (a TPU:
    ``interpret`` False) or asked for (``use_pallas``), the stream is
    non-empty, values are 32-bit, a row block's F-tile is one the chip
    can block (all of F, a multiple of 128 lanes, or one column at a time
    through the scalar kernel), and the kernel's
    VMEM footprint (``plan.fused_vmem_bytes``) fits ``vmem_budget`` —
    the fast level of the executor's ``HardwareModel`` (None: the
    attached device's, ``HardwareModel.attached``), which is also the
    ``vmem_limit_bytes`` the kernel is compiled with. ``"jnp"`` — the
    XLA scatter sweep — otherwise. A row block of width 1 runs the scalar
    kernel.
    """
    m = values_shape[0]
    feat = values_shape[1] if len(values_shape) == 2 else 0
    if m == 0 or (interpret and not use_pallas):
        return "jnp"
    if jnp.dtype(dtype).itemsize != 4 or (len(values_shape) == 2 and feat == 0):
        return "jnp"
    budget = vmem_budget or HardwareModel.attached().fast_levels[-1]
    r = bin_range or FUSED_BIN_RANGE
    ft = 0
    if feat > 1:
        ft = max(1, min(feat, f_tile or feat))
        if not (interpret or ft in (1, feat) or ft % 128 == 0):
            return "jnp"
    return "pallas" if fused_vmem_bytes(out_size, bin_range=r, f_tile=ft) <= budget else "jnp"


def _fused_reduce_jnp(
    indices: jnp.ndarray,
    values: jnp.ndarray,
    out_size: int,
    op: str,
    sorted_within: Optional[int] = None,
    in_bounds: bool = False,
) -> jnp.ndarray:
    """The jnp realization of the fused reduce: the whole stream reduced
    straight into the dense output by one XLA scatter (or, for a sorted
    in-bounds add stream, one sorted segment-sum). The binned
    intermediate is never built. ``sorted_within <= 1`` hands XLA the
    elementwise sortedness fact when the caller actually guarantees it,
    and ``in_bounds=True`` is the caller's promise that every index lies
    in ``[0, out_size)`` (a CSR/CSC-derived stream guarantees this by
    construction), letting the scatter skip per-update bounds masking.
    The default keeps the drop-out-of-range semantics every other method
    shares.
    """
    vshape = pb.value_block_shape(values)  # raises on unsupported ranks
    ident = pb.reduce_identity(op, values.dtype)
    out0 = jnp.full((out_size,) + vshape, ident, values.dtype)
    if indices.shape[0] == 0:
        return out0
    srt = sorted_within is not None and sorted_within <= 1
    if op == "add" and srt and in_bounds:
        # a binned (elementwise-sorted, in-bounds) add stream is a
        # segmented reduction, not a scatter: XLA's sorted segment-sum
        # walks the output sequentially — the jnp rendering of what
        # consuming the binned stream buys (bit-exact with the scatter
        # form: both accumulate in stream order within a segment)
        from repro import compat

        return compat.segment_sum(
            values, indices, num_segments=out_size,
            # sorted-ok: branch gated on `srt` (caller's sorted_within
            indices_are_sorted=True,  # claim), checked by REPRO_PB_CHECK
        ).astype(values.dtype)
    upd = out0.at[indices]
    apply = {"add": upd.add, "min": upd.min, "max": upd.max}[op]
    # the contract checker verifies the promise under REPRO_PB_CHECK:
    # in-bounds-ok: gated on the caller's explicit in_bounds claim
    mode = "promise_in_bounds" if in_bounds else "drop"
    return apply(values, indices_are_sorted=srt, mode=mode)


def execute_reduce(
    indices: jnp.ndarray,
    values: jnp.ndarray,
    *,
    out_size: int,
    op: str = "add",
    method: str = "fused",
    bin_range: Optional[int] = None,
    num_bins: Optional[int] = None,
    plan: Optional[CobraPlan] = None,
    block: int = 2048,
    interpret: Optional[bool] = None,
    use_pallas: bool = False,
    sorted_within: Optional[int] = None,
    f_tile: Optional[int] = None,
    in_bounds: bool = False,
    vmem_budget: Optional[int] = None,
) -> jnp.ndarray:
    """Reduce one (indices, values) stream to a dense (out_size, ...) array.

    The traceable core of ``PBExecutor.reduce_stream``. ``method`` is any
    of ``REDUCE_METHODS``: the binning methods run the classic two-phase
    pipeline (``execute_binning`` + ``pb.bin_read_reduce``); ``fused``
    runs the single-sweep bin-and-accumulate in the realization
    ``fused_realization`` names: the Pallas C-Buffer kernel when the
    backend compiles it (a TPU: ``interpret`` resolves False) or
    ``use_pallas`` asks for it, and it fits ``vmem_budget``; the
    jnp scatter sweep otherwise. Only commutative ops are accepted:
    order-sensitive consumers must use ``bin_stream`` (DESIGN.md §8).

    Row-block ``(m, F)`` values flow through every method (DESIGN.md
    §14): the fused Pallas realization is the feature-tiled row-block
    kernel (``f_tile`` columns per stream sweep), the jnp sweep carries
    rows natively, and the two-phase Bin-Read reduce always has.
    ``in_bounds=True`` is the caller's promise that indices lie in
    ``[0, out_size)``, unlocking the maskless scatter fast path.
    """
    if op not in REDUCE_OPS:
        raise ValueError(
            f"reduce_stream only serves commutative reductions {REDUCE_OPS}; "
            f"got op={op!r}. Non-commutative consumers need the stable "
            "two-phase path: bin_stream() + an order-aware Bin-Read."
        )
    if method not in REDUCE_METHODS:
        raise ValueError(
            f"unknown reduce method: {method!r} (want one of {REDUCE_METHODS})"
        )
    interpret = resolve_interpret(interpret)
    if method == "fused":
        vshape = pb.value_block_shape(values)  # raises on unsupported ranks
        r = bin_range or FUSED_BIN_RANGE
        budget = vmem_budget or HardwareModel.attached().fast_levels[-1]
        realization = fused_realization(
            out_size, values.shape, values.dtype, interpret=interpret,
            use_pallas=use_pallas, bin_range=r, f_tile=f_tile, vmem_budget=budget,
        )
        if realization == "pallas":
            from repro.kernels import fused as fused_kernels

            kw = dict(
                num_indices=out_size, bin_range=r, op=op, block=FUSED_BLOCK,
                cap=FUSED_CAP, interpret=interpret, vmem_limit_bytes=budget,
            )
            if vshape in ((), (1,)):  # a width-1 row block is a scalar lane
                out = fused_kernels.cobra_bin_accumulate_pallas(
                    indices, values.reshape(-1), **kw
                )
                return out.reshape((out_size,) + vshape)
            ft = max(1, min(vshape[0], f_tile or vshape[0]))
            if ft == 1:
                # the narrowest tile: one scalar sweep per column, for rows
                # whose 128-lane tile does not fit
                return jnp.stack([
                    fused_kernels.cobra_bin_accumulate_pallas(indices, values[:, c], **kw)
                    for c in range(vshape[0])
                ], axis=1)
            return fused_kernels.cobra_bin_accumulate_rows_pallas(
                indices, values, f_tile=ft, **kw,
            )
        return _fused_reduce_jnp(
            indices, values, out_size, op,
            sorted_within=sorted_within, in_bounds=in_bounds,
        )
    r = bin_range or max(1, min(512, out_size))
    nb = num_bins or -(-out_size // r)
    bins = execute_binning(
        indices,
        values,
        bin_range=r,
        num_bins=nb,
        method=method,
        plan=plan,
        block=block,
        interpret=interpret,
    )
    if bins.idx.shape[0] == 0:
        return jnp.full(
            (out_size,) + values.shape[1:], pb.reduce_identity(op, values.dtype),
            values.dtype,
        )
    # static order guarantee: binning leaves the stream bin-blocked at the
    # effective range (bins.bin_range may be a tracer through inner jits)
    eff_range = plan.final_bin_range if (method == "hierarchical" and plan) else r
    sw = sorted_within if sorted_within is not None else eff_range
    return pb.bin_read_reduce(
        bins, out_size, op=op, out_dtype=values.dtype, sorted_within=sw
    )


class BatchedBins(NamedTuple):
    """A batch of binned streams (leading batch axis on every field).

    The batched analogue of ``pb.Bins`` for serving-style traffic: many
    small frontiers binned under ONE executor decision.
    """

    idx: jnp.ndarray  # (B, m)
    val: jnp.ndarray  # (B, m, ...)
    starts: jnp.ndarray  # (B, num_bins+1)
    bin_range: int


@functools.partial(
    jax.jit, static_argnames=("bin_range", "num_bins", "method", "block")
)
def _binning_batched(indices, values, bin_range, num_bins, method, block):
    def one(ix, vx):
        b = execute_binning(
            ix, vx, bin_range=bin_range, num_bins=num_bins, method=method, block=block
        )
        return b.idx, b.val, b.starts

    return jax.vmap(one)(indices, values)


def bin_streams_batched(
    indices: jnp.ndarray,
    values,
    *,
    bin_range: int,
    num_bins: int,
    method: str = "sort",
    block: int = 2048,
) -> BatchedBins:
    """vmap the binning core over a leading batch axis.

    Only the pure-XLA methods batch (``sort``/``counting``); the Pallas
    and multi-pass paths are per-stream. One (method, bin_range) decision
    serves the whole batch — planning amortized across frontiers.
    """
    if method not in ("sort", "counting"):
        raise ValueError(f"batched binning supports sort|counting, got {method!r}")
    idx, val, starts = _binning_batched(
        indices, values, bin_range, num_bins, method, block
    )
    return BatchedBins(idx=idx, val=val, starts=starts, bin_range=bin_range)


# ---------------------------------------------------------------------------
# Dispatch routing (MoE): Binning of a (token, expert) assignment stream.
# ---------------------------------------------------------------------------


def dispatch_permutation(
    key: jnp.ndarray, num_slots: int, method: str = "sort", block: int = 2048
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stable counting-sort routing for capacity-bounded dispatch.

    This is the paper's Binning phase (Algorithm 2 line "insert into
    bin") applied to MoE expert dispatch (DESIGN.md §3.2): ``key[a]`` is
    the slot of assignment ``a`` in ``[0, num_slots]``, where slot
    ``num_slots`` is the overflow bin for assignments routed elsewhere.

    Returns ``(order, key_sorted, starts, rank)``:
      order       stable permutation grouping assignments by slot;
      key_sorted  ``key[order]``;
      starts      (num_slots+2,) exclusive prefix of slot counts;
      rank        in-slot arrival rank of each sorted assignment (the
                  per-bin cursor value — used for capacity clipping).

    ``method="sort"`` uses XLA argsort; ``method="counting"`` uses the
    blockwise counting-sort permutation (`pb.counting_permutation`), the
    PB-structured path the Pallas kernels implement. Both are stable, so
    the routing (and therefore model numerics) is method-independent.
    """
    a = key.shape[0]
    nb = num_slots + 1
    if method == "counting":
        dest, counts = pb.counting_permutation(key, nb, block=block)
        starts = pb.starts_from_counts(counts)
        order = jnp.zeros((a,), jnp.int32).at[dest].set(
            jnp.arange(a, dtype=jnp.int32)
        )
    elif method == "sort":
        order = jnp.argsort(key, stable=True)
        starts = pb.starts_from_counts(jnp.bincount(key, length=nb).astype(jnp.int32))
    else:
        raise ValueError(
            f"unknown dispatch method: {method!r} (want 'sort' or 'counting')"
        )
    key_s = jnp.take(key, order)
    rank = jnp.arange(a, dtype=jnp.int32) - jnp.take(starts, key_s)
    return order, key_s, starts, rank


# ---------------------------------------------------------------------------
# Decisions, fallback table, autotune cache.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BinningDecision:
    """What the executor chose for one stream shape, and why.

    ``pipeline_chunks`` is the sharded-exchange pipeline depth K
    (DESIGN.md §13): 1 everywhere except mesh-sharded reduce decisions,
    where the roofline overlap model (or a measured sweep under the
    topology-extended ``:pipeline`` cache key) picks how many
    double-buffered chunks the owner exchange splits into.

    ``f_tile`` is the row-block feature-tile width (DESIGN.md §14): 0 for
    scalar-lane streams; for ``(m, F)`` row-block reduce decisions the
    number of feature columns resident per fused stream sweep (the
    stream is re-read ``ceil(F / f_tile)`` times)."""

    method: str
    bin_range: int
    num_bins: int
    plan: Optional[CobraPlan]
    source: str  # analytic | fallback-table | autotuned | cache
    pipeline_chunks: int = 1
    f_tile: int = 0

    def describe(self) -> str:
        ft = f"/f{self.f_tile}" if self.f_tile else ""
        return f"{self.method}@r{self.bin_range}{ft}[{self.source}]"


def _bucket(x: int) -> int:
    return max(0, int(math.log2(x))) if x > 0 else 0


# In-repo fallback table: (log2 num_indices, log2 stream_len) -> method.
# Seeded from interpret-mode measurements on this container (see
# benchmarks/executor_autotune.py); consulted when no measured cache
# entry exists and autotuning is off — e.g. cold start on a read-only
# filesystem. Coarse on purpose: buckets not listed fall through to the
# analytic model (DESIGN.md §3.1).
_FALLBACK_TABLE = {
    (8, 10): "sort",
    (8, 12): "sort",
    (10, 12): "sort",
    (10, 14): "counting",
    (12, 14): "counting",
    (12, 16): "counting",
    (14, 16): "hierarchical",
    (14, 18): "hierarchical",
    (16, 17): "hierarchical",
    (16, 18): "hierarchical",
    (16, 20): "hierarchical",
    (18, 20): "hierarchical",
    (20, 22): "hierarchical",
}


# Persisted-cache schema version. Bump on ANY change to the _key format:
# entries under an old key format would never be looked up again, yet
# merge-on-save would preserve them forever — versioning discards the
# whole stale file instead. v2: reduce keys bucket stream_len (§11.3).
# v3: row-block reduce keys carry the feature dim F (§14) — a method
# measured on a scalar lane is not evidence about an F-wide row stream.
_CACHE_SCHEMA_VERSION = 3


class _AutotuneCache:
    """Measured-decision cache: in-memory dict + best-effort JSON persistence.

    Per-process entries always work; the on-disk layer degrades silently
    (read-only HOME, exotic containers) so the executor never fails a
    workload over a cache write.
    """

    def __init__(self, cache_dir: Optional[str] = None):
        self.dir = (
            cache_dir
            or os.environ.get("REPRO_PB_CACHE_DIR")
            or os.path.join(os.path.expanduser("~"), ".cache", "repro_pb")
        )
        self.path = os.path.join(self.dir, "autotune.json")
        self.mem: dict = {}
        self.persist_ok = True
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                blob = json.load(f)
            if isinstance(blob, dict) and blob.get("version") == _CACHE_SCHEMA_VERSION:
                self.mem.update(blob.get("entries", {}))
        except (OSError, ValueError):
            pass

    def _save(self) -> None:
        """Merge-on-save under an advisory lock: concurrent writers (the
        8-device subprocess tests, parallel benchmark runs) each
        measured *different* keys; the old read-once/overwrite-forever
        dropped every entry another process persisted in between. Each
        save re-reads the file, layers this process's entries on top,
        and atomically replaces — with an ``flock`` around the
        read-merge-write so two interleaved savers cannot race the
        window between read and replace (on a conflicting key the later
        saver wins: both values are real measurements of the same
        shape). Locking degrades to best-effort merge where flock is
        unavailable; persistence itself degrades silently as before."""
        if not self.persist_ok:
            return
        try:
            os.makedirs(self.dir, exist_ok=True)
            with open(self.path + ".lock", "w") as lockf:
                try:
                    import fcntl

                    fcntl.flock(lockf, fcntl.LOCK_EX)  # released on close
                except (ImportError, OSError):
                    pass  # no flock (non-POSIX): merge still applies
                merged: dict = {}
                try:
                    with open(self.path) as f:
                        blob = json.load(f)
                    if isinstance(blob, dict) and blob.get("version") == _CACHE_SCHEMA_VERSION:
                        merged.update(blob.get("entries", {}))
                except (OSError, ValueError):
                    pass  # no file yet / torn read: nothing to merge
                merged.update(self.mem)
                tmp = f"{self.path}.tmp.{os.getpid()}"  # per-process tmp
                with open(tmp, "w") as f:
                    json.dump(
                        {"version": _CACHE_SCHEMA_VERSION, "entries": merged},
                        f,
                        indent=1,
                    )
                os.replace(tmp, self.path)
        except OSError:
            self.persist_ok = False  # degrade to in-memory only

    def get(self, key: str) -> Optional[dict]:
        return self.mem.get(key)

    def put(self, key: str, entry: dict) -> None:
        self.mem[key] = entry
        self._save()


class HostStreamStats:
    """The host share of the streams a traversal hands its executor,
    summed over calls until ``reset``: seconds spent expanding frontiers
    and building level streams in host numpy, uploads to the device
    included (the ``traversal.host_stream`` spans); the bytes uploaded
    and the levels expanded (those spans' ``upload_bytes`` and
    ``levels`` counters). Reading a level's result back to the host
    waits on the device, so it is not in ``seconds``."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.seconds = 0.0
        self.upload_bytes = 0
        self.levels = 0

    @contextlib.contextmanager
    def timed(self):
        sp = spans.span("traversal.host_stream")
        try:
            with sp:
                yield sp
        finally:
            self.seconds += sp.seconds
            self.upload_bytes += sp.counters.get("upload_bytes", 0)
            self.levels += sp.counters.get("levels", 0)

    def upload(self, a: np.ndarray) -> jnp.ndarray:
        with self.timed():
            spans.count("upload_bytes", a.nbytes)
            # waited for, so the copy is in ``seconds``
            return jnp.asarray(a).block_until_ready()


# ---------------------------------------------------------------------------
# The executor.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _jitted_binning(bin_range, num_bins, method, block, interpret, plan):
    def f(idx, val):
        return execute_binning(
            idx,
            val,
            bin_range=bin_range,
            num_bins=num_bins,
            method=method,
            plan=plan,
            block=block,
            interpret=interpret,
        )

    return jax.jit(f)


@functools.lru_cache(maxsize=256)
def _jitted_reduce_batched(
    out_size, bin_range, num_bins, method, op, block, interpret, sorted_within,
    vmem_budget,
):
    """The reduce core over a leading batch axis. A ``fused`` reduce
    whose lane realizes as ``"pallas"`` (``fused_realization`` at
    ``vmem_budget``) runs the C-Buffer kernel once per lane, since a
    Pallas grid does not vmap. Every other method, and the jnp sweep,
    vmap through ``execute_reduce`` / ``_fused_reduce_jnp``.

    Why the kernel, where it fits, and not the vmapped scatter (one v5e,
    PERF.md §5): per compiled program the kernel costs 0.5-1.1 s of
    compile and 48 ns per tuple, the scatter 6-11 s and 11 ns. A serving
    process compiles one program per (lanes, bucket, op), and the
    scatter's faster sweep repays its compile only past ~2e8 tuples
    through that program; a cold frontier level sees far fewer."""

    def batched(idx, val):
        if method == "fused" and fused_realization(
            out_size, val.shape[1:], val.dtype, interpret=interpret,
            bin_range=bin_range, vmem_budget=vmem_budget,
        ) == "pallas":
            return jnp.stack([
                execute_reduce(
                    idx[q], val[q], out_size=out_size, op=op, method="fused",
                    bin_range=bin_range, interpret=interpret,
                    vmem_budget=vmem_budget,
                )
                for q in range(idx.shape[0])
            ])
        return jax.vmap(one)(idx, val)

    def one(idx, val):
        if method == "fused":
            return _fused_reduce_jnp(
                idx, val, out_size, op, sorted_within=sorted_within
            )
        return execute_reduce(
            idx,
            val,
            out_size=out_size,
            op=op,
            method=method,
            bin_range=bin_range,
            num_bins=num_bins,
            block=block,
            interpret=interpret,
            use_pallas=False,
            sorted_within=sorted_within,
        )

    return jax.jit(batched)


@functools.lru_cache(maxsize=256)
def _jitted_reduce(
    out_size, bin_range, num_bins, method, op, block, interpret, plan, use_pallas,
    sorted_within, f_tile=None, in_bounds=False, vmem_budget=None,
):
    def f(idx, val):
        return execute_reduce(
            idx,
            val,
            out_size=out_size,
            op=op,
            method=method,
            bin_range=bin_range,
            num_bins=num_bins,
            plan=plan,
            block=block,
            interpret=interpret,
            use_pallas=use_pallas,
            sorted_within=sorted_within,
            f_tile=f_tile,
            in_bounds=in_bounds,
            vmem_budget=vmem_budget,
        )

    return jax.jit(f)


class PBExecutor:
    """Plan-driven (and optionally measured) PB execution.

    One instance per hardware model; consumers share the process-wide
    default from ``get_default_executor()``. ``autotune=True`` makes
    ``decide`` measure every candidate method on a synthetic stream of
    the requested shape (once per key; results cached and persisted).
    ``hw=None`` takes the model of the attached TPU by its
    ``device_kind`` (an unknown kind is an error); other backends model
    the v5e the repo targets.
    """

    def __init__(
        self,
        hw: Optional[HardwareModel] = None,
        *,
        autotune: bool = False,
        cache_dir: Optional[str] = None,
        use_pallas: bool = False,
        block: int = 2048,
        interpret: Optional[bool] = None,
    ):
        self.hw = hw or HardwareModel.attached()
        self.autotune = autotune
        self.use_pallas = use_pallas
        self.block = block
        self.interpret = resolve_interpret(interpret)
        self.cache = _AutotuneCache(cache_dir)
        # every decide() appends here — benchmarks/run.py serializes it
        # into BENCH_smoke.json so PRs have a method-decision trajectory
        self.decision_log: list = []
        # caller-managed side channels (see add_decision_sink): unlike
        # decision_log they are not capped, so a consumer that needs an
        # exact per-call trace (PreprocessPipeline stage reports) still
        # sees decisions after the shared log saturates
        self._decision_sinks: list = []
        self._last_entry: Optional[dict] = None
        # host seconds and upload bytes of traversal level streams
        # (core/traversal.py), read per phase by chip_smoke.py
        self.host_streams = HostStreamStats()

    # -- decision ----------------------------------------------------------

    def _key(
        self,
        num_indices: int,
        stream_len: int,
        dtype,
        bin_range: Optional[int] = None,
        kind: str = "bin",
        op: str = "add",
        mesh_shape: Optional[Tuple[Tuple[str, int], ...]] = None,
        feature_dim: int = 0,
    ) -> str:
        # bin_range is part of the key: a method measured at one range is
        # not evidence about another (counting's cost is ~linear in the
        # C-Buffer fan-out, i.e. in num_indices/bin_range). ``kind``
        # separates reduction entries (the fused candidate exists there,
        # dtype is the VALUE dtype, and the op shapes the apply cost)
        # from pure binning entries in the persisted cache schema.
        # Device topology is always part of the key: a method measured on
        # one device is not evidence about a sharded run (the per-device
        # stream/domain shrink with the mesh, DESIGN.md §9), and a mesh
        # decision must never be replayed on a different topology.
        topo = f"d{jax.device_count()}"
        if mesh_shape:
            topo += "/" + "x".join(f"{a}{s}" for a, s in mesh_shape)
        # Frontier policy (DESIGN.md §11): reduction streams arrive at
        # every length a traversal level produces, so reduce entries key
        # on the log2 BUCKET of stream_len — the same bucketing the
        # fallback table uses. A short frontier then never replays a
        # full-stream cache entry (different bucket), while nearby
        # lengths share one measured decision instead of retuning per
        # level. Binning entries keep the exact length (their consumers
        # are whole-stream).
        sl = f"b{_bucket(stream_len)}" if kind != "bin" else str(stream_len)
        base = (
            f"{num_indices}:{sl}:{jnp.dtype(dtype).name}:"
            f"{jax.default_backend()}:{topo}"
        )
        if kind != "bin":
            base = f"{base}:{kind}:{op}"
            if feature_dim > 1:
                # row-block streams: the feature dim scales the apply
                # traffic AND the accumulator footprint (DESIGN.md §14),
                # so F-wide decisions never share scalar-lane entries.
                # F=1 shares the scalar key on purpose: one value per
                # index is the scalar economics (same accumulator bytes,
                # f_tile trivially 1), and serving warmup enumerates
                # scalar keys only.
                base = f"{base}:f{feature_dim}"
        return f"{base}:r{bin_range}" if bin_range else base

    def _candidates(self, flat_values: bool, kind: str = "bin") -> Tuple[str, ...]:
        c = ["sort", "counting"]
        if self.use_pallas and flat_values:
            c.append("pallas")
        c.append("hierarchical")
        if kind in ("reduce", "update"):
            # update (delta-merge) streams are reductions over the same
            # pipelines, so the fused single sweep competes there too
            c.append("fused")
        return tuple(c)

    def _finalize(
        self, method: str, num_indices: int, bin_range: Optional[int], source: str
    ) -> BinningDecision:
        """Attach the range/plan to a chosen method (paper §3: flat
        methods run at the compromise range unless the caller fixed one;
        §4: hierarchical always ends at the Bin-Read-optimal range)."""
        if method == "hierarchical":
            plan = CobraPlan.from_hardware(
                num_indices, self.hw, final_bin_range=bin_range
            )
            return BinningDecision(
                method, plan.final_bin_range, plan.num_bins, plan, source
            )
        if method == "fused":
            # the fused method's range is its C-Buffer geometry
            default = FUSED_BIN_RANGE
        else:
            default = compromise_bin_range(num_indices, self.hw)
        r = bin_range or max(1, min(default, num_indices))
        return BinningDecision(method, r, num_bins_for_range(num_indices, r), None, source)

    def analytic_method(
        self, num_indices: int, stream_len: int, bin_range: Optional[int] = None
    ) -> str:
        """The DESIGN.md §3.1 decision tree (no measurement), evaluated
        at the *effective* range — a caller-fixed ``bin_range`` changes
        the fan-out and therefore the right method."""
        if stream_len < _SORT_THRESHOLD or num_indices <= 1:
            return "sort"
        r = bin_range or max(
            1, min(compromise_bin_range(num_indices, self.hw), num_indices)
        )
        if num_bins_for_range(num_indices, r) <= binning_optimal_num_bins(self.hw):
            return "pallas" if self.use_pallas else "counting"
        return "hierarchical"

    def fused_fits(
        self,
        num_indices: int,
        itemsize: int = 4,
        f_tile: int = 0,
        bin_range: Optional[int] = None,
    ) -> bool:
        """Fusion legality, capacity half (DESIGN.md §8.1): the fused
        kernel's footprint (``plan.fused_vmem_bytes``: accumulator plus
        C-Buffers, at ``f_tile`` columns for a row block) fits the largest
        fast level — on a TPU the only level, VMEM, and the limit the
        kernel is compiled with."""
        return fused_fits(
            self.hw, num_indices, bin_range=bin_range or FUSED_BIN_RANGE,
            itemsize=itemsize, f_tile=f_tile,
        )

    def analytic_reduce_method(
        self,
        num_indices: int,
        stream_len: int,
        bin_range: Optional[int] = None,
        itemsize: int = 4,
        f_tile: int = 0,
    ) -> str:
        """DESIGN.md §8: the fused single sweep strictly halves stream
        bytes whenever its accumulator fits the fast level, so it wins
        every bandwidth-bound case; oversized domains fall back to the
        two-phase tree at §3.1. A row-block stream is checked at the
        F-tile the policy picks (``choose_f_tile``), never at full F:
        feature tiling (§14) caps what must be resident."""
        if self.fused_fits(num_indices, itemsize, f_tile, bin_range):
            return "fused"
        return self.analytic_method(num_indices, stream_len, bin_range)

    def choose_f_tile(
        self,
        feature_dim: int,
        num_indices: int,
        itemsize: int = 4,
    ) -> int:
        """F-tiling policy (DESIGN.md §14): the widest slab of feature
        columns the compiled kernel can block — all of F, a multiple of
        the 128-lane register width, or one column (the scalar kernel per
        column) — whose footprint (``plan.fused_vmem_bytes``) fits the
        fast level. The F-tile loop is OUTERMOST, so the binned index
        stream is re-streamed ``ceil(F / f_tile)`` times; wider tiles
        amortize those re-reads, which is why the policy maximizes rather
        than minimizes. Where nothing fits, the single column
        (``fused_fits`` then refuses the fused method). Returns 0 for
        scalar (``feature_dim == 0``) streams."""
        if feature_dim <= 0:
            return 0
        lanes = 128
        wide = range((feature_dim - 1) // lanes * lanes, 0, -lanes)
        for ft in (feature_dim, *wide):
            if self.fused_fits(num_indices, itemsize, ft):
                return ft
        return 1

    def decide(
        self,
        num_indices: int,
        stream_len: int,
        dtype=jnp.int32,
        *,
        bin_range: Optional[int] = None,
        flat_values: bool = True,
        kind: str = "bin",
        op: str = "add",
        mesh_shape: Optional[Tuple[Tuple[str, int], ...]] = None,
        feature_dim: int = 0,
    ) -> BinningDecision:
        """Pick (method, bin_range, plan) for a stream shape.

        Priority: measured cache -> live autotune (if enabled) ->
        in-repo fallback table -> analytic hardware model. ``kind`` is
        "bin" for stream binning or "reduce" for dense reductions, where
        the fused single-sweep method joins the candidate set, ``dtype``
        is the value dtype, and ``op`` keys the cache entry.
        ``mesh_shape`` (tuples of (axis, size)) keys sharded decisions by
        device topology; single-device keys still carry the process's
        device count (DESIGN.md §9). ``feature_dim`` is F for row-block
        ``(m, F)`` value streams (0 = scalar lane): it extends the cache
        key, scales the fused-legality check, and stamps the decision's
        ``f_tile`` axis (DESIGN.md §14).
        """
        key = self._key(
            num_indices, stream_len, dtype, bin_range, kind, op, mesh_shape,
            feature_dim,
        )
        d = self._decide_uncached(
            key, num_indices, stream_len, dtype, bin_range, flat_values, kind, op,
            feature_dim,
        )
        if kind == "reduce" and feature_dim:
            d = _dc_replace(
                d,
                f_tile=self.choose_f_tile(
                    feature_dim, num_indices, jnp.dtype(dtype).itemsize
                ),
            )
        if mesh_shape and kind == "reduce":
            # the pipeline-depth axis of a sharded decision (DESIGN.md
            # §13): measured entry under the topology-extended key when
            # one exists, else the roofline overlap model
            d = _dc_replace(
                d,
                pipeline_chunks=self._pipeline_chunks_for(
                    key, num_indices, stream_len, mesh_shape
                ),
            )
        entry = {
            "kind": kind,
            "num_indices": num_indices,
            "stream_len": stream_len,
            "method": d.method,
            "bin_range": d.bin_range,
            "source": d.source,
        }
        if kind != "bin":
            entry["op"] = op
        if feature_dim:
            entry["feature_dim"] = feature_dim
            entry["f_tile"] = d.f_tile
        if mesh_shape:
            entry["mesh"] = {a: s for a, s in mesh_shape}
            if kind == "reduce":
                entry["pipeline_chunks"] = d.pipeline_chunks
        vshape = (feature_dim,) if feature_dim else ()
        entry.update(
            self._realization(
                num_indices, (stream_len,) + vshape, dtype, d, sharded=bool(mesh_shape)
            )
        )
        self._log_decision(entry)
        return d

    def _realization(
        self, num_indices: int, values_shape, dtype, d: BinningDecision,
        sharded: bool = False,
    ) -> dict:
        """``{"realization": ...}`` for a fused decision (empty for any
        other method): what one ``execute_reduce`` of this stream shape
        runs under this executor. A sharded decision's device-local
        reduce runs as ``distributed_pb`` calls it (default budget, no
        ``use_pallas``); ``reduce_streams`` names its own per-lane one."""
        if d.method != "fused":
            return {}
        return {
            "realization": fused_realization(
                num_indices, tuple(values_shape), dtype, interpret=self.interpret,
                use_pallas=self.use_pallas and not sharded, bin_range=d.bin_range,
                f_tile=d.f_tile or None,
                vmem_budget=None if sharded else self.hw.fast_levels[-1],
            )
        }

    def _log_decision(self, entry: dict) -> None:
        """Append one decision record to the bounded shared log and every
        registered uncapped sink. The entry object is also remembered so
        ``shard_reduce_stream`` can enrich ITS decision record in place
        with post-run exchange facts (chosen capacity, overflow) — same
        dict everywhere, so log and sinks both see the update."""
        self._last_entry = entry
        if len(self.decision_log) < _DECISION_LOG_CAP:
            self.decision_log.append(entry)
        for sink in self._decision_sinks:
            sink.append(entry)

    def add_decision_sink(self, sink: list) -> None:
        """Register an uncapped side channel that every subsequent
        ``decide`` appends its log entry to. Callers own the list's
        lifetime and MUST detach it (``remove_decision_sink``) when done
        — used by ``PreprocessPipeline`` to attribute decisions to
        stages even after ``decision_log`` hits its cap."""
        self._decision_sinks.append(sink)

    def remove_decision_sink(self, sink: list) -> None:
        # identity, not equality: nested sinks receive the same entries
        # and compare ==, so list.remove would detach the wrong one
        for i, s in enumerate(self._decision_sinks):
            if s is sink:
                del self._decision_sinks[i]
                return
        raise ValueError("sink not registered")

    def decide_or_forced(
        self,
        method: Optional[str],
        num_indices: int,
        stream_len: int,
        dtype=jnp.int32,
        *,
        bin_range: Optional[int] = None,
        flat_values: bool = True,
        kind: str = "bin",
        op: str = "add",
        mesh_shape: Optional[Tuple[Tuple[str, int], ...]] = None,
        feature_dim: int = 0,
    ) -> BinningDecision:
        """``decide`` when the caller passed ``None``/"auto", else the
        caller-forced method finalized at this shape — the one branch
        every consumer entry point (pagerank, components, sharded
        kernels) needs, kept here so none of them reach into
        ``_finalize`` directly."""
        if method in (None, "auto"):
            return self.decide(
                num_indices, stream_len, dtype, bin_range=bin_range,
                flat_values=flat_values, kind=kind, op=op, mesh_shape=mesh_shape,
                feature_dim=feature_dim,
            )
        d = self._finalize(method, num_indices, bin_range, "caller")
        if kind == "reduce" and feature_dim:
            d = _dc_replace(
                d,
                f_tile=self.choose_f_tile(
                    feature_dim, num_indices, jnp.dtype(dtype).itemsize
                ),
            )
        return d

    def _decide_uncached(
        self, key, num_indices, stream_len, dtype, bin_range, flat_values, kind, op,
        feature_dim: int = 0,
    ) -> BinningDecision:
        hit = self.cache.get(key)
        if hit is not None and hit.get("method") in self._candidates(flat_values, kind):
            return self._finalize(hit["method"], num_indices, bin_range, "cache")
        if self.autotune and stream_len > 0:
            entry = self.measure_methods(
                num_indices, stream_len, dtype, bin_range, flat_values, kind=kind,
                op=op, feature_dim=feature_dim,
            )
            self.cache.put(key, entry)
            return self._finalize(entry["method"], num_indices, bin_range, "autotuned")
        # The fallback table is bucketed on the *default* (compromise)
        # range; a caller-fixed range changes the fan-out, so skip the
        # table and evaluate the analytic tree at that range instead.
        # (Binning only: reduce decisions have no measured table yet.)
        if bin_range is None and kind == "bin":
            tkey = (_bucket(num_indices), _bucket(stream_len))
            m = _FALLBACK_TABLE.get(tkey)
            if m is not None and m in self._candidates(flat_values, kind):
                return self._finalize(m, num_indices, bin_range, "fallback-table")
        if kind != "bin":
            # fused legality at the F-TILE the policy would pick, not at
            # full F: tiling is exactly what keeps wide rows resident.
            # kind="update" (delta-merge streams) shares the reduce
            # economics — only the cache key namespace differs.
            isz = jnp.dtype(dtype).itemsize
            ft = self.choose_f_tile(feature_dim, num_indices, isz)
            analytic = self.analytic_reduce_method(
                num_indices, stream_len, bin_range, itemsize=isz, f_tile=ft
            )
        else:
            analytic = self.analytic_method(num_indices, stream_len, bin_range)
        return self._finalize(analytic, num_indices, bin_range, "analytic")

    # -- pipeline depth (sharded exchange, DESIGN.md §13) ------------------

    def _pipeline_chunks_for(
        self,
        key: str,
        num_indices: int,
        stream_len: int,
        mesh_shape: Tuple[Tuple[str, int], ...],
    ) -> int:
        """K for a sharded reduce decision: the measured ``:pipeline``
        cache entry when one exists (written by ``_tune_pipeline_chunks``
        under the same topology-extended key), else the roofline overlap
        model evaluated at the global stream shape."""
        n_dev = 1
        for _, s in mesh_shape:
            n_dev *= int(s)
        if n_dev <= 1 or stream_len <= 0:
            return 1
        hit = self.cache.get(f"{key}:pipeline")
        if hit is not None and "pipeline_chunks" in hit:
            return max(1, int(hit["pipeline_chunks"]))
        from repro.roofline import ShardedPBStreamRoofline

        rl = ShardedPBStreamRoofline(
            num_tuples=max(1, stream_len),
            num_indices=max(1, num_indices * n_dev),
            n_dev=n_dev,
        )
        return rl.best_pipeline_chunks()

    def _tune_pipeline_chunks(
        self,
        key: str,
        indices,
        values,
        *,
        out_size: int,
        mesh,
        op: str,
        axis_name: Optional[str],
        d: BinningDecision,
        capacity: int,
    ) -> int:
        """Measure K ∈ {1, 2, 4} on the REAL stream and mesh, persist the
        winner under ``key:pipeline``. This is how the autotuner learns
        that K=1 beats pipelining on tiny streams (per-chunk collective
        launch overhead dominates) without trusting the model."""
        hit = self.cache.get(f"{key}:pipeline")
        if hit is not None and "pipeline_chunks" in hit:
            return max(1, int(hit["pipeline_chunks"]))
        from repro.core import distributed_pb as dpb

        timings: dict = {}
        for k in (1, 2, 4):
            def run():
                return dpb.shard_reduce_stream(
                    indices, values, out_size=out_size, mesh=mesh, op=op,
                    axis_name=axis_name, method=d.method,
                    bin_range=d.bin_range, plan=d.plan, capacity=capacity,
                    block=self.block, pipeline_chunks=k,
                )

            try:
                jax.block_until_ready(run())  # compile + warm
                ts = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run())
                    ts.append(time.perf_counter() - t0)
                timings[str(k)] = min(ts) * 1e6
            # a chunking arm can be unsupported on a backend; the sweep
            # must try the rest, and the arm missing from `timings` is
            # the recorded trace of the failure
            # pb-lint: disable=PB006
            except Exception:
                continue
        if not timings:
            return 1
        best = int(min(timings, key=timings.get))
        self.cache.put(
            f"{key}:pipeline", {"pipeline_chunks": best, "timings_us": timings}
        )
        return best

    # -- autotune measurement ---------------------------------------------

    def measure_methods(
        self,
        num_indices,
        stream_len,
        dtype=jnp.int32,
        bin_range=None,
        flat_values=True,
        reps: int = 3,
        kind: str = "bin",
        op: str = "add",
        feature_dim: int = 0,
    ) -> dict:
        """Time every candidate method on a synthetic stream of this
        shape; returns ``{"method": best, "timings_us": {...}}``. The
        measured answer to the paper's §3 compromise — used by ``decide``
        when autotuning and by benchmarks/executor_autotune.py.
        ``kind="reduce"`` times the dense-reduction pipelines (including
        the fused single sweep) instead of bare binning; ``feature_dim``
        probes with (m, F) row-block values so a row decision is measured
        on row traffic (DESIGN.md §14)."""
        rng = np.random.default_rng(num_indices * 1_000_003 + stream_len)
        idx = jnp.asarray(
            rng.integers(0, max(1, num_indices), stream_len), jnp.int32
        )
        if feature_dim:
            val = jnp.arange(stream_len * feature_dim, dtype=dtype).reshape(
                stream_len, feature_dim
            )
        else:
            val = jnp.arange(stream_len, dtype=dtype)
        isz = jnp.dtype(dtype).itemsize
        ftile = self.choose_f_tile(feature_dim, num_indices, isz) or None
        timings = {}
        for method in self._candidates(flat_values, kind):
            d = self._finalize(method, num_indices, bin_range, "probe")
            if kind != "bin":
                fn = _jitted_reduce(
                    num_indices, d.bin_range, d.num_bins, method, op, self.block,
                    self.interpret, d.plan, self.use_pallas, None, ftile, False,
                    self.hw.fast_levels[-1],
                )
            else:
                fn = _jitted_binning(
                    d.bin_range, d.num_bins, method, self.block, self.interpret, d.plan
                )
            try:
                jax.block_until_ready(fn(idx, val))  # compile + warm
                ts = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(idx, val))
                    ts.append(time.perf_counter() - t0)
                timings[method] = min(ts) * 1e6
            # a method may be unsupported on a backend; the measurement
            # sweep must continue, and the method's absence from
            # `timings` is the recorded outcome of the failure
            # pb-lint: disable=PB006
            except Exception:
                continue
        best = min(timings, key=timings.get) if timings else "sort"
        return {"method": best, "timings_us": timings}

    # -- contracts (DESIGN.md §16.2) ---------------------------------------

    def _check_contract(
        self,
        indices,
        values,
        num_nodes: int,
        d: BinningDecision,
        *,
        op: str = "add",
        sorted_within: Optional[int] = None,
        in_bounds: bool = False,
    ) -> None:
        """Validate the stream against the decision before running it.

        The cheap structural subset (binning geometry, value rank,
        fused-accumulator legality, cache-key completeness) is always
        on; ``REPRO_PB_CHECK=1`` adds the data-touching claims
        (in-bounds promise, sortedness) — see
        ``repro.analysis.contracts.check_stream``. Violations raise a
        typed ``ContractError`` carrying ``d.describe()``. Pytree value
        streams are checked index-side only (their leaves are binned
        leafwise and carry no rank policy).
        """
        from repro.analysis import contracts

        vals = (
            values
            if hasattr(values, "shape") and hasattr(values, "dtype")
            else np.zeros((int(indices.shape[0]),), np.int32)
        )
        contracts.check_stream(
            indices, vals, num_nodes, d, op=op,
            sorted_within=sorted_within, in_bounds=in_bounds, hw=self.hw,
        )

    # -- execution ---------------------------------------------------------

    def bin_stream(
        self,
        indices: jnp.ndarray,
        values,
        *,
        num_indices: int,
        bin_range: Optional[int] = None,
        method: Optional[str] = None,
    ) -> pb.Bins:
        """Bin one stream. The single call path every workload uses
        (pagerank, components, neighbor_populate, benchmarks).

        ``method=None`` (or "auto") consults ``decide``; an explicit
        method skips planning but still routes through the shared core.
        An eager call is the span ``pb.bin_stream``.
        """
        with spans.span(
            "pb.bin_stream", over=(indices,), stream_len=int(indices.shape[0]),
            num_indices=num_indices,
        ) as sp:
            return self._bin_stream(indices, values, sp, num_indices, bin_range, method)

    def _bin_stream(self, indices, values, sp, num_indices, bin_range, method) -> pb.Bins:
        flat = isinstance(values, jnp.ndarray) and values.ndim == 1
        if method in (None, "auto"):
            d = self.decide(
                num_indices,
                int(indices.shape[0]),
                indices.dtype,
                bin_range=bin_range,
                flat_values=flat,
            )
        else:
            d = self._finalize(method, num_indices, bin_range, "caller")
        sp.set(method=d.method, bin_range=d.bin_range, source=d.source)
        fn = _jitted_binning(
            d.bin_range, d.num_bins, d.method, self.block, self.interpret, d.plan
        )
        b = fn(indices, values)
        return pb.Bins(b.idx, b.val, b.starts, d.bin_range)

    def bin_streams(
        self,
        indices: jnp.ndarray,
        values,
        *,
        num_indices: int,
        bin_range: Optional[int] = None,
        method: Optional[str] = None,
    ) -> BatchedBins:
        """Batched-frontier path: indices (B, m). One decision for the
        whole batch (restricted to the vmap-able methods)."""
        # per-stream values are 1-D iff the batched array is (B, m);
        # (B, m, d) row values are NOT flat — the decision must know
        flat = isinstance(values, jnp.ndarray) and values.ndim == 2
        feat = (
            int(values.shape[2])
            if isinstance(values, jnp.ndarray) and values.ndim == 3
            else 0
        )
        if method in (None, "auto"):
            d = self.decide(
                num_indices,
                int(indices.shape[1]),
                indices.dtype,
                bin_range=bin_range,
                flat_values=flat,
            )
            if d.method not in ("sort", "counting"):
                # only the pure-XLA methods vmap; clamp to sort AND log
                # the clamp under its own source tag so decision_log /
                # BENCH rows report what actually ran, not the pre-clamp
                # choice. Row-valued clamps also record the requested F
                # and the F-tile the fused path WOULD have used, so an
                # autotune regression is diagnosable from the log alone
                # (DESIGN.md §14).
                d = self._finalize(
                    "sort", num_indices, bin_range, f"{d.source}+batch-clamp"
                )
                entry = {
                    "kind": "bin",
                    "num_indices": num_indices,
                    "stream_len": int(indices.shape[1]),
                    "method": d.method,
                    "bin_range": d.bin_range,
                    "source": d.source,
                }
                if feat:
                    entry["feature_dim"] = feat
                    entry["f_tile"] = self.choose_f_tile(feat, num_indices)
                self._log_decision(entry)
        else:
            d = self._finalize(method, num_indices, bin_range, "caller")
        return bin_streams_batched(
            indices,
            values,
            bin_range=d.bin_range,
            num_bins=d.num_bins,
            method=d.method,
            block=self.block,
        )

    def reduce_stream(
        self,
        indices: jnp.ndarray,
        values: jnp.ndarray,
        *,
        out_size: int,
        op: str = "add",
        bin_range: Optional[int] = None,
        method: Optional[str] = None,
        sorted_within: Optional[int] = None,
        in_bounds: bool = False,
        kind: str = "reduce",
    ) -> jnp.ndarray:
        """Reduce one commutative stream to a dense (out_size, ...) array.

        The fifth method, ``fused``, is the single-sweep
        bin-and-accumulate (kernels/fused.py) — no binned intermediate in
        HBM, roughly half the stream bytes of the two-phase pipeline
        (DESIGN.md §8). ``method=None``/"auto" consults ``decide`` with
        the reduce candidate set; non-commutative ops are rejected (use
        ``bin_stream``). ``sorted_within`` is the caller's true order
        guarantee (1 = elementwise sorted indices); ``in_bounds`` its
        promise that indices lie in ``[0, out_size)`` (CSR/CSC streams).

        Row-block ``(m, F)`` values route through the feature-tiled
        fused realization: ``decide`` keys on F, checks fused legality at
        the chosen F-tile, and stamps ``f_tile`` on the decision
        (DESIGN.md §14).

        ``kind`` tags the decision namespace: "reduce" (default) or
        "update" for graph-mutation delta-merge streams (DESIGN.md §15)
        — same candidate set and pipelines, but update streams get their
        own cache keys (their index distribution is batch-shaped, not
        edge-shaped) and their own decision-log records, so
        BENCH_smoke.json can attribute method choices to mutation
        traffic. Forced-method update calls still log (source="caller"):
        the mutation trail must be visible even when the caller pinned
        the method. An eager call is the span ``pb.reduce_stream``.
        """
        with spans.span(
            "pb.reduce_stream", over=(indices,), stream_len=int(indices.shape[0]),
            out_size=out_size, op=op, kind=kind,
        ) as sp:
            return self._reduce_stream(
                indices, values, sp, out_size, op, bin_range, method,
                sorted_within, in_bounds, kind,
            )

    def _reduce_stream(
        self, indices, values, sp, out_size, op, bin_range, method,
        sorted_within, in_bounds, kind,
    ) -> jnp.ndarray:
        if op not in REDUCE_OPS:
            raise ValueError(
                f"reduce_stream only serves commutative reductions {REDUCE_OPS}; "
                f"got op={op!r}. Non-commutative consumers need the stable "
                "two-phase path: bin_stream() + an order-aware Bin-Read."
            )
        if kind not in ("reduce", "update"):
            raise ValueError(
                f"reduce_stream kind must be 'reduce' or 'update', got {kind!r}"
            )
        vshape = (
            pb.value_block_shape(values)
            if isinstance(values, (jnp.ndarray, np.ndarray))
            else ()
        )
        flat = isinstance(values, jnp.ndarray) and vshape == ()
        feat = vshape[0] if vshape else 0
        vdtype = values.dtype if hasattr(values, "dtype") else jnp.float32
        if method in (None, "auto"):
            d = self.decide(
                out_size,
                int(indices.shape[0]),
                vdtype,  # the VALUE dtype: it sizes the apply traffic
                bin_range=bin_range,
                flat_values=flat,
                kind=kind,
                op=op,
                feature_dim=feat,
            )
        else:
            d = self._finalize(method, out_size, bin_range, "caller")
            if feat:
                d = _dc_replace(
                    d,
                    f_tile=self.choose_f_tile(
                        feat, out_size, jnp.dtype(vdtype).itemsize
                    ),
                )
            if kind == "update":
                self._log_decision(
                    {
                        "kind": kind,
                        "num_indices": out_size,
                        "stream_len": int(indices.shape[0]),
                        "method": d.method,
                        "bin_range": d.bin_range,
                        "source": d.source,
                        "op": op,
                        **self._realization(out_size, values.shape, vdtype, d),
                    }
                )
        if not flat and d.method != "fused":
            # the two-phase Bin-Read reduce handles row values too, but
            # pallas binning is 1-D-only; route those to sort
            if d.method == "pallas":
                d = self._finalize("sort", out_size, bin_range, d.source)
        self._check_contract(
            indices, values, out_size, d, op=op,
            sorted_within=sorted_within, in_bounds=in_bounds,
        )
        sp.set(method=d.method, bin_range=d.bin_range, source=d.source)
        fn = _jitted_reduce(
            out_size, d.bin_range, d.num_bins, d.method, op, self.block,
            self.interpret, d.plan, self.use_pallas, sorted_within,
            d.f_tile or None, in_bounds, self.hw.fast_levels[-1],
        )
        return fn(indices, values)

    # Reduce methods that survive vmap: the pure-XLA two-phase pair plus
    # the jnp rendering of the fused sweep. pallas/hierarchical are
    # per-stream (kernel grids / multi-pass plans don't batch).
    BATCHED_REDUCE_METHODS = ("sort", "counting", "fused")

    def reduce_streams(
        self,
        indices: jnp.ndarray,
        values: jnp.ndarray,
        *,
        out_size: int,
        op: str = "add",
        bin_range: Optional[int] = None,
        method: Optional[str] = None,
        sorted_within: Optional[int] = None,
    ) -> jnp.ndarray:
        """Batched reduce over (B, m) streams -> (B, out_size, ...).

        The serving-side counterpart of ``bin_streams`` (DESIGN.md §12):
        many small frontiers — one per coalesced query — reduced under
        ONE decision and ONE compiled vmap program, so per-query
        planning cost is amortized across the batch. Each lane computes
        exactly what ``reduce_stream`` at the same (method, bin_range)
        would: the binning permutation depends on indices alone and the
        apply runs per lane, so batched-vs-loop results are bit-for-bit
        equal (tests/test_property.py asserts it). Methods outside
        ``BATCHED_REDUCE_METHODS`` clamp to ``sort`` under a
        ``+batch-clamp`` source tag, mirroring ``bin_streams``.
        """
        if op not in REDUCE_OPS:
            raise ValueError(
                f"reduce_streams only serves commutative reductions "
                f"{REDUCE_OPS}; got op={op!r}."
            )
        if indices.ndim != 2:
            raise ValueError(
                f"reduce_streams wants (B, m) indices, got {indices.shape}"
            )
        flat = isinstance(values, jnp.ndarray) and values.ndim == 2
        feat = (
            int(values.shape[2])
            if isinstance(values, jnp.ndarray) and values.ndim == 3
            else 0
        )
        if method in (None, "auto"):
            vdtype = values.dtype if hasattr(values, "dtype") else jnp.float32
            d = self.decide(
                out_size,
                int(indices.shape[1]),
                vdtype,
                bin_range=bin_range,
                flat_values=flat,
                kind="reduce",
                op=op,
                feature_dim=feat,
            )
            if d.method not in self.BATCHED_REDUCE_METHODS:
                d = self._finalize(
                    "sort", out_size, bin_range, f"{d.source}+batch-clamp"
                )
                entry = {
                    "kind": "reduce",
                    "num_indices": out_size,
                    "stream_len": int(indices.shape[1]),
                    "method": d.method,
                    "bin_range": d.bin_range,
                    "source": d.source,
                    "op": op,
                }
                if feat:
                    entry["feature_dim"] = feat
                    entry["f_tile"] = self.choose_f_tile(feat, out_size)
                self._log_decision(entry)
        else:
            if method not in self.BATCHED_REDUCE_METHODS:
                raise ValueError(
                    f"batched reduce supports {self.BATCHED_REDUCE_METHODS}, "
                    f"got {method!r}"
                )
            d = self._finalize(method, out_size, bin_range, "caller")
        if d.method == "fused" and method in (None, "auto"):
            # per lane, as ``_jitted_reduce_batched`` realizes it: the
            # compiled kernel where it is compiled (a TPU) and fits at
            # full F; ``use_pallas`` never interprets it per lane
            self._last_entry["realization"] = fused_realization(
                out_size, tuple(values.shape[1:]), values.dtype,
                interpret=self.interpret, bin_range=d.bin_range,
                vmem_budget=self.hw.fast_levels[-1],
            )
        fn = _jitted_reduce_batched(
            out_size, d.bin_range, d.num_bins, d.method, op, self.block,
            self.interpret, sorted_within, self.hw.fast_levels[-1],
        )
        return fn(indices, values)

    def shard_reduce_stream(
        self,
        indices: jnp.ndarray,
        values: jnp.ndarray,
        *,
        out_size: int,
        mesh=None,
        op: str = "add",
        axis_name: Optional[str] = None,
        bin_range: Optional[int] = None,
        method: Optional[str] = None,
        capacity: Optional[int] = None,
        pipeline_chunks: Optional[int] = None,
        packed: bool = True,
    ) -> jnp.ndarray:
        """Mesh-sharded commutative reduction (DESIGN.md §9, §13): the
        device shard is the coarsest C-Buffer level, the interconnect its
        eviction path (``core/distributed_pb.py``). ``decide`` picks the
        device-local method at the PER-DEVICE shape (owned index range,
        received stream length) under a topology-extended cache key, so
        single-device autotune decisions are never replayed for sharded
        runs; the same decision carries the exchange pipeline depth K
        (``pipeline_chunks=None``: measured ``:pipeline`` cache entry,
        live-tuned when autotuning, else the roofline overlap model).
        ``capacity=None`` estimates the per-destination segment size from
        owner skew, guarded by the overflow fallback; the chosen
        capacity/K/overflow are recorded on this call's decision-log
        entry. ``mesh=None`` or one device degrades to ``reduce_stream``
        bit-stably.
        """
        from repro.core import distributed_pb as dpb

        if op not in REDUCE_OPS:
            raise ValueError(
                f"shard_reduce_stream only serves commutative reductions "
                f"{REDUCE_OPS}; got op={op!r}. Non-commutative consumers "
                "need the stable exchange + an order-aware Bin-Read "
                "(see distributed_pb.shard_build_csr)."
            )
        n_dev = (
            1
            if mesh is None
            else int(mesh.shape[dpb.resolve_stream_axis(mesh, axis_name)])
        )
        if mesh is None or n_dev == 1:
            return self.reduce_stream(
                indices, values, out_size=out_size, op=op, bin_range=bin_range,
                method=method,
            )
        m = int(indices.shape[0])
        r = dpb.shard_range_for(out_size, n_dev)
        cap_src = "caller" if capacity is not None else "estimated"
        cap = (
            int(capacity)
            if capacity is not None
            else dpb.estimate_capacity(indices, out_size=out_size, n_dev=n_dev)
        ) if m > 0 else 1
        vshape = (
            pb.value_block_shape(values)
            if isinstance(values, (jnp.ndarray, np.ndarray))
            else ()
        )
        flat = isinstance(values, jnp.ndarray) and vshape == ()
        feat = vshape[0] if vshape else 0
        vdtype = values.dtype if hasattr(values, "dtype") else jnp.float32
        mesh_shape = tuple(sorted(mesh.shape.items()))
        entry: Optional[dict] = None
        if method in (None, "auto"):
            d = self.decide(
                r,  # per-device domain: the owned index range
                n_dev * cap,  # per-device stream: the padded received exchange
                vdtype,
                bin_range=bin_range,
                flat_values=flat,
                kind="reduce",
                op=op,
                mesh_shape=mesh_shape,
                feature_dim=feat,
            )
            entry = self._last_entry  # enriched with exchange facts below
        else:
            d = self._finalize(method, r, bin_range, "caller")
        if not flat and d.method == "pallas":  # pallas binning is 1-D-only
            d = self._finalize("sort", r, bin_range, d.source)
        # per-device contract: the decision's binning geometry must cover
        # the owned index range r (the device-local domain, DESIGN.md §9)
        self._check_contract(indices, values, r, d, op=op)
        k = pipeline_chunks
        if k is None:
            key = self._key(r, n_dev * cap, vdtype, bin_range, "reduce", op, mesh_shape)
            if self.autotune and m > 0:
                k = self._tune_pipeline_chunks(
                    key, indices, values, out_size=out_size, mesh=mesh, op=op,
                    axis_name=axis_name, d=d, capacity=cap,
                )
            elif method in (None, "auto"):
                k = d.pipeline_chunks
            else:
                k = self._pipeline_chunks_for(key, r, n_dev * cap, mesh_shape)
        out, xinfo = dpb.shard_reduce_stream_info(
            indices,
            values,
            out_size=out_size,
            mesh=mesh,
            op=op,
            axis_name=axis_name,
            method=d.method,
            bin_range=d.bin_range,
            capacity=cap,  # the capacity the decision was keyed on
            block=self.block,
            plan=d.plan,
            pipeline_chunks=k,
            packed=packed,
        )
        xfields = {
            "capacity": xinfo["capacity"],
            "capacity_source": (
                "overflow-fallback" if xinfo["fallback"] else cap_src
            ),
            "pipeline_chunks": xinfo["pipeline_chunks"],
            "overflow": xinfo["overflow"],
            "packed": xinfo["packed"],
        }
        if entry is not None:
            # same dict object the log and every sink hold: the decision
            # record gains the exchange facts (PreprocessReport surfaces
            # overflow this way)
            entry.update(xfields)
        else:  # forced method: no decide() entry exists — append one
            self._log_decision(
                {
                    "kind": "shard_exchange",
                    "num_indices": out_size,
                    "stream_len": m,
                    "method": "exchange",
                    "bin_range": 0,
                    "source": xfields["capacity_source"],
                    "op": op,
                    "mesh": {a: s for a, s in mesh_shape},
                    **xfields,
                }
            )
        return out

    def scatter_add(
        self,
        indices: jnp.ndarray,
        values: jnp.ndarray,
        *,
        out_size: int,
        bin_range: Optional[int] = None,
        method: Optional[str] = None,
    ) -> jnp.ndarray:
        """Full PB scatter-add (Binning + commutative Bin-Read), the
        paper's Fig. 1 pipeline for additive updates. Routes through
        ``reduce_stream`` so additive consumers get the fused single
        sweep whenever ``decide`` picks it."""
        return self.reduce_stream(
            indices,
            values,
            out_size=out_size,
            op="add",
            bin_range=bin_range,
            method=method,
        )

    def scatter_add_batched(
        self,
        indices: jnp.ndarray,
        values: jnp.ndarray,
        *,
        out_size: int,
        bin_range: Optional[int] = None,
    ) -> jnp.ndarray:
        """Batched scatter-add over (B, m) streams -> (B, out_size)."""
        bb = self.bin_streams(
            indices, values, num_indices=out_size, bin_range=bin_range
        )

        def one(ix, vx):
            out = jnp.zeros((out_size,) + vx.shape[1:], vx.dtype)
            return out.at[ix].add(vx)

        return jax.vmap(one)(bb.idx, bb.val)


_DEFAULT: Optional[PBExecutor] = None


def get_default_executor() -> PBExecutor:
    """Process-wide executor. ``REPRO_PB_AUTOTUNE=1`` turns on measured
    selection; ``REPRO_PB_USE_PALLAS=1`` adds the Pallas kernels to the
    candidate set (interpret-mode on CPU containers)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = PBExecutor(
            autotune=os.environ.get("REPRO_PB_AUTOTUNE", "0") == "1",
            use_pallas=os.environ.get("REPRO_PB_USE_PALLAS", "0") == "1",
        )
    return _DEFAULT


def set_default_executor(ex: Optional[PBExecutor]) -> None:
    """Swap the process-wide executor (tests, notebooks)."""
    global _DEFAULT
    _DEFAULT = ex
