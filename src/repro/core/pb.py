"""Propagation Blocking (PB) primitives — the paper's Algorithm 2, TPU-idiomatic.

PB splits an irregular update stream into:

  Binning  — route each (index, value) tuple into the bin owning
             ``index // bin_range``, coalescing writes so all memory
             traffic is sequential.
  Bin-Read — process bins one at a time; each bin's touched index range
             fits in fast memory.

On a multicore, Binning appends to bins through per-bin cursors; the TPU
equivalent is a **stable counting sort by bin id** (histogram → exclusive
prefix → rank-and-permute). Stability is what preserves correctness for
non-commutative kernels (paper §2): tuples within a bin keep stream order.

Two implementations are provided:

  * ``binning_sort``     — semantic reference built on XLA's stable sort.
  * ``binning_counting`` — the PB-structured blockwise implementation: a
    ``lax.scan`` over fixed-size blocks, each block maintaining per-bin
    cursors ("C-Buffer" state) in registers/VMEM. This is the algorithm
    the Pallas kernel (kernels/binning) implements on real TPUs, and the
    building block of the hierarchical COBRA execution (core/cobra.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Bins(NamedTuple):
    """A binned tuple stream.

    idx/val are the stream reordered so bin 0's tuples come first (stable
    within each bin). ``starts`` has length num_bins+1 (exclusive prefix
    of per-bin counts).
    """

    idx: jnp.ndarray
    val: jnp.ndarray
    starts: jnp.ndarray
    bin_range: int

    @property
    def num_bins(self) -> int:
        return int(self.starts.shape[0]) - 1


def bin_ids(indices: jnp.ndarray, bin_range: int) -> jnp.ndarray:
    return (indices // bin_range).astype(jnp.int32)


def reduce_identity(op: str, dtype) -> jnp.ndarray:
    """Identity element of a commutative reduce op — what untouched
    output indices hold. The single definition every reduce path
    (executor fallback, fused kernel, Bin-Read, test oracle) shares."""
    dt = jnp.dtype(dtype)
    if op == "add":
        return jnp.zeros((), dt)
    if op == "min":
        big = jnp.iinfo(dt).max if jnp.issubdtype(dt, jnp.integer) else jnp.finfo(dt).max
        return jnp.array(big, dt)
    if op == "max":
        small = (
            jnp.iinfo(dt).min if jnp.issubdtype(dt, jnp.integer) else jnp.finfo(dt).min
        )
        return jnp.array(small, dt)
    raise ValueError(f"unknown reduce op: {op!r} (want 'add', 'min' or 'max')")


def starts_from_counts(counts: jnp.ndarray) -> jnp.ndarray:
    z = jnp.zeros((1,), dtype=jnp.int32)
    return jnp.concatenate([z, jnp.cumsum(counts, dtype=jnp.int32)])


def value_block_shape(values) -> Tuple[int, ...]:
    """Per-element value shape of a stream's value array: ``()`` for a
    scalar lane (rank 1), ``(F,)`` for a dense row block (rank 2).

    The ONE place the supported-value-rank policy lives. Every consumer
    that branches on "flat vs row" or builds a stream ``pad_width`` goes
    through here, so an unsupported rank fails loudly at the entry point
    instead of silently falling through a hardcoded ``ndim in {1, 2}``
    check somewhere downstream (rank-3+ tensor values would need their
    own C-Buffer layout — DESIGN.md §14).
    """
    ndim = getattr(values, "ndim", None)
    if ndim is None:
        raise TypeError(
            f"stream values must be an array, got {type(values).__name__} "
            "(pytree values are handled leafwise by the binning paths)"
        )
    if ndim == 1:
        return ()
    if ndim == 2:
        return (int(values.shape[1]),)
    raise ValueError(
        "stream values must be rank-1 (scalar lane) or rank-2 (row "
        f"block, one dense feature row per tuple); got rank {ndim} with "
        f"shape {tuple(values.shape)}"
    )


# ---------------------------------------------------------------------------
# Reference binning: XLA stable sort by bin id.
# ---------------------------------------------------------------------------


def binning_sort(
    indices: jnp.ndarray, values: jnp.ndarray, bin_range: int, num_bins: int
) -> Bins:
    bids = bin_ids(indices, bin_range)
    perm = jnp.argsort(bids, stable=True)
    counts = jnp.bincount(bids, length=num_bins).astype(jnp.int32)
    return Bins(
        idx=jnp.take(indices, perm),
        val=jax.tree.map(lambda v: jnp.take(v, perm, axis=0), values),
        starts=starts_from_counts(counts),
        bin_range=bin_range,
    )


# ---------------------------------------------------------------------------
# PB-structured binning: blockwise counting sort with per-bin cursors.
# ---------------------------------------------------------------------------


def _pad_stream(x: jnp.ndarray, block: int, fill) -> jnp.ndarray:
    # value_block_shape enforces the supported ranks (scalar lane / row
    # block) — padding a rank the reduce paths would then mishandle must
    # fail HERE, not produce a silently wrong fallback downstream
    vshape = value_block_shape(x)
    m = x.shape[0]
    pad = (-m) % block
    if pad == 0:
        return x
    pad_width = [(0, pad)] + [(0, 0)] * len(vshape)
    return jnp.pad(x, pad_width, constant_values=fill)


def counting_permutation(
    bids: jnp.ndarray, num_bins: int, block: int = 2048
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Destination position of every element under a stable counting sort
    by ``bids``; also returns per-bin counts.

    Structure mirrors PB's Binning phase: a scan over blocks, carrying a
    per-bin write cursor. Within a block, one-hot ranks are computed with
    dense ops (MXU-friendly on TPU; the Pallas kernel keeps the one-hot
    tile in VMEM).
    """
    m = bids.shape[0]
    counts = jnp.bincount(bids, length=num_bins).astype(jnp.int32)
    cursors0 = starts_from_counts(counts)[:-1]  # (B,) write cursor per bin

    bids_p = _pad_stream(bids, block, num_bins)  # padding routed to bin B
    nblocks = bids_p.shape[0] // block
    blocks = bids_p.reshape(nblocks, block)

    def step(cursors, kb):
        oh = (kb[:, None] == jnp.arange(num_bins, dtype=kb.dtype)[None, :]).astype(
            jnp.int32
        )  # (block, B)
        in_block_rank = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=1) - 1  # (block,)
        base = jnp.where(kb < num_bins, cursors[jnp.minimum(kb, num_bins - 1)], m)
        pos = base + in_block_rank
        return cursors + jnp.sum(oh, axis=0), pos

    _, pos_blocks = jax.lax.scan(step, cursors0, blocks)
    dest = pos_blocks.reshape(-1)[:m]
    return dest, counts


def inverse_permutation(dest: jnp.ndarray) -> jnp.ndarray:
    """inv with inv[dest[i]] = i, via ONE int32 scatter (no argsort).

    Turns every subsequent placement ``out[dest] = v`` into the gather
    ``v[inv]`` — gathers need no zero-initialized destination, so the
    dead memset per value leaf disappears from the counting path."""
    m = dest.shape[0]
    return jnp.zeros((m,), jnp.int32).at[dest].set(jnp.arange(m, dtype=jnp.int32))


def binning_counting(
    indices: jnp.ndarray,
    values,
    bin_range: int,
    num_bins: int,
    block: int = 2048,
) -> Bins:
    bids = bin_ids(indices, bin_range)
    dest, counts = counting_permutation(bids, num_bins, block=block)
    inv = inverse_permutation(dest)

    def place(v):
        return jnp.take(v, inv, axis=0)

    return Bins(
        idx=place(indices),
        val=jax.tree.map(place, values),
        starts=starts_from_counts(counts),
        bin_range=bin_range,
    )


def binning(
    indices: jnp.ndarray,
    values,
    bin_range: int,
    num_bins: int,
    method: str = "sort",
    block: int = 2048,
) -> Bins:
    if method == "sort":
        return binning_sort(indices, values, bin_range, num_bins)
    if method == "counting":
        return binning_counting(indices, values, bin_range, num_bins, block=block)
    raise ValueError(f"unknown binning method: {method}")


# ---------------------------------------------------------------------------
# Bin-Read helpers.
# ---------------------------------------------------------------------------


def segment_ids_from_starts(starts: jnp.ndarray, stream_len: int) -> jnp.ndarray:
    return jnp.searchsorted(
        starts[1:], jnp.arange(stream_len, dtype=jnp.int32), side="right"
    ).astype(jnp.int32)


def bin_read_scatter_add(
    bins: Bins, out_size: int, out_dtype=jnp.float32, sorted_within: int | None = None
):
    """Commutative Bin-Read: accumulate binned values into a dense output.

    Because the stream is sorted by bin (and bins are contiguous index
    ranges), the scatter walks the output nearly sequentially — the
    locality PB buys. What binning actually guarantees is *bin-blocked*
    order: indices sorted at granularity ``bin_range``, not elementwise —
    so XLA's ``indices_are_sorted`` (a full-sortedness claim) is only
    legal when the granularity is 1. ``sorted_within`` carries that true
    guarantee: it defaults to ``bins.bin_range`` and a caller that knows
    a tighter order (e.g. a stream pre-sorted by exact index) passes 1 to
    hand XLA the fact when it actually holds.

    Pytree values reduce leafwise (one dense output per leaf), matching
    what ``binning_sort``/``binning_counting`` accept on the way in.
    """
    return bin_read_reduce(
        bins, out_size, op="add", out_dtype=out_dtype, sorted_within=sorted_within
    )


# Tuples per row of ``pairwise_segment_sum``'s scan: one 128-lane row.
_SCAN_ROW = 128


def _shift_back(x: jnp.ndarray, s: int, axis: int, fill) -> jnp.ndarray:
    """``x`` moved ``s`` places up ``axis``, the first ``s`` filled."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (s, 0)
    head = jax.lax.slice_in_dim(x, 0, x.shape[axis] - s, axis=axis)
    return jnp.pad(head, pad, constant_values=fill)


def _segmented_scan(keys: jnp.ndarray, values: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inclusive scan of ``values`` along ``axis`` within runs of equal
    ``keys`` (sorted along that axis, at least 0): Hillis-Steele, each
    element adds the one ``s`` places back for ``s`` = 1, 2, 4, ... while
    that one is in its run, so every output is a balanced tree sum."""
    n = keys.shape[axis]
    extra = (None,) * (values.ndim - keys.ndim)
    s = 1
    while s < n:
        back = (_shift_back(keys, s, axis, -1) == keys)[(Ellipsis,) + extra]
        values = values + jnp.where(back, _shift_back(values, s, axis, 0), 0)
        s *= 2
    return values


def pairwise_segment_sum(
    indices: jnp.ndarray, values: jnp.ndarray, out_size: int, *, presorted: bool = False
) -> jnp.ndarray:
    """``out[i] = sum of values[j] where indices[j] == i``, each index's
    terms summed along a tree of depth about log2 of the stream length,
    so that the rounding error of an inexact sum grows with the log of
    an index's tuple count, not with the count itself.

    A scatter-add accumulates an index's terms one after another and
    rounds each partial sum: at an index with a million float32 terms the
    result drifts by parts per thousand. Here the stream is sorted by
    index (unless the caller says it is, ``presorted``); on a TPU, XLA's
    own scatter-add sorts its stream first as well. The sorted stream is
    cut into rows of ``_SCAN_ROW`` tuples, and each row scanned within
    its runs of one index (``_segmented_scan``). The rows' last elements
    are scanned the same way across rows, and each row's first run adds
    what the rows before it hold of that index. The last element of each
    index's run then holds its sum, and one sorted scatter of those run
    ends writes the output. Indices outside ``[0, out_size)``, after the
    usual wrap of negative ones, are dropped, as the scatter drops them.
    Values are rank-1 or rank-2 (a row per tuple); nothing is computed
    in another precision.
    """
    vshape = value_block_shape(values)
    m = indices.shape[0]
    out = jnp.zeros((out_size,) + vshape, values.dtype)
    if m == 0:
        return out
    idx = jnp.where(indices < 0, indices + out_size, indices)
    idx = jnp.where((idx < 0) | (idx >= out_size), out_size, idx).astype(jnp.int32)
    if not presorted:
        if vshape:
            idx, perm = jax.lax.sort((idx, jnp.arange(m, dtype=jnp.int32)), is_stable=False)
            values = jnp.take(values, perm, axis=0)
        else:
            idx, values = jax.lax.sort((idx, values), is_stable=False)
    # padding sorts after every index and is dropped with the out-of-range
    pad = (-m) % _SCAN_ROW
    if pad:
        idx = jnp.pad(idx, (0, pad), constant_values=out_size + 1)
        values = jnp.pad(values, [(0, pad)] + [(0, 0)] * len(vshape))
    keys = idx.reshape(-1, _SCAN_ROW)
    rows = _segmented_scan(keys, values.reshape(keys.shape + vshape), axis=1)
    # what the rows before each row hold of the index that ends them
    carry = _segmented_scan(keys[:, -1], rows[:, -1], axis=0)
    into = _shift_back(keys[:, -1], 1, 0, -1)[:, None] == keys
    extra = (None,) * len(vshape)
    rows = rows + jnp.where(into[(Ellipsis,) + extra], _shift_back(carry, 1, 0, 0)[:, None], 0)
    run_end = jnp.concatenate([idx[1:] != idx[:-1], jnp.ones((1,), bool)])
    ends = jnp.where(run_end[(slice(None),) + extra], rows.reshape(values.shape), 0)
    # sorted-ok: idx was sorted above, or the caller promised it (presorted)
    return out.at[idx].add(ends, indices_are_sorted=True)


def bin_read_reduce(
    bins: Bins,
    out_size: int,
    op: str = "add",
    out_dtype=None,
    sorted_within: int | None = None,
):
    """Commutative Bin-Read for any supported reduction (add | min | max).

    The two-phase counterpart of the fused single-sweep path
    (``kernels/fused.py``): same result, one extra HBM round-trip for the
    binned stream. Untouched indices hold the op's identity (zeros for
    ``add``). Values may be a pytree — each leaf is reduced into its own
    dense ``(out_size, ...)`` output, mirroring the pytree support of the
    binning phase.
    """
    sw = bins.bin_range if sorted_within is None else sorted_within
    if op not in ("add", "min", "max"):
        reduce_identity(op, jnp.float32)  # raises the canonical error

    def one(v: jnp.ndarray) -> jnp.ndarray:
        dt = jnp.dtype(out_dtype or v.dtype)
        if op == "add" and jnp.issubdtype(dt, jnp.inexact):
            return pairwise_segment_sum(bins.idx, v.astype(dt), out_size, presorted=sw <= 1)
        if op == "add":
            out = jnp.zeros((out_size,) + v.shape[1:], dtype=dt)
            return out.at[bins.idx].add(v.astype(dt), indices_are_sorted=sw <= 1)
        out = jnp.full((out_size,) + v.shape[1:], reduce_identity(op, dt), dtype=dt)
        upd = out.at[bins.idx]
        apply = upd.min if op == "min" else upd.max
        return apply(v.astype(dt), indices_are_sorted=sw <= 1)

    return jax.tree.map(one, bins.val)


@functools.partial(jax.jit, static_argnames=("out_size", "num_bins", "bin_range"))
def full_pb_scatter_add(indices, values, out_size, *, bin_range, num_bins):
    b = binning_sort(indices, values, bin_range, num_bins)
    return bin_read_scatter_add(b, out_size, out_dtype=values.dtype)
