"""Fused single-sweep PB: bin-and-accumulate without the HBM intermediate.

The two-phase pipeline (``kernels/binning.py`` + a Bin-Read scatter) pays
two full HBM sweeps of the edge stream: Binning writes the reordered
``(idx, val)`` tuples out, Bin-Read reads them back. For **commutative**
reductions (add, min, max) the binned stream never needs to exist: the
paper's C-Buffers can absorb the irregularity on chip and a buffer flush
can *reduce* its tuples into a dense per-bin accumulator tile instead of
appending them to an HBM bin. That is what ``cobra_bin_accumulate``
does — COBRA's §4 eviction path with the binning engine's write
retargeted at a per-bin accumulator that stays in VMEM for the whole
pass and is copied to HBM once (DESIGN.md §8).

Structure (as the TPU's Mosaic compiler accepts it):

  * the stream's indices arrive in SMEM blocks, so the per-tuple append
    is scalar work: bin id ``idx // bin_range``, fill level (SMEM), and
    one masked lane-row store into the bin's C-Buffer in VMEM
    (``cb_off``: bin-local offsets, ``cb_val``: values; ``cap`` tuples
    per bin, ``cap / 128`` lane rows);
  * a C-Buffer that fills is *flushed by reduction* at once: its tuples
    are turned onto sublanes (one transpose) and reduced against a
    one-hot of each accumulator lane row — dense VPU work (the row-block
    ``add`` flush is a one-hot matmul on the MXU), no HBM traffic;
  * the accumulator is VMEM scratch for the whole grid; a trailing drain
    step flushes every partly filled C-Buffer and DMAs the accumulator
    to the HBM output once.

Legality: the reduction operator must be commutative (tuples reach the
accumulator in flush order, not stream order) and accumulator plus
C-Buffers (``core/plan.py::fused_vmem_bytes``) must fit the VMEM budget the kernel is
compiled with. The executor checks both (``core/executor.py``,
DESIGN.md §8). Indices outside ``[0, num_indices)`` are dropped, as in
every other reduce path.

Float ``add`` sums in flush order, so results agree with the dense
scatter oracle (``kernels/ref.py::scatter_reduce_ref``) to rounding;
integer sums and ``min``/``max`` agree exactly. ``interpret`` resolves
through ``repro.kernels.resolve_interpret``.

``cobra_bin_accumulate_rows_pallas`` is the row-block (SpMM)
generalization: values carry a dense feature row of width F and the
accumulator becomes a feature-tiled (V_tile × F_tile) C-Buffer — the
kernel behind GNN neighbor aggregation (DESIGN.md §14).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# single shared definition of the op set and identities (core/pb.py)
from repro.core.pb import reduce_identity
from repro.core.plan import FUSED_BIN_RANGE, FUSED_BLOCK, FUSED_CAP
from repro.kernels import resolve_interpret

_FUSED_OPS = ("add", "min", "max")
_LANES = 128

_COMBINE = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
_REDUCE = {"add": jnp.sum, "min": jnp.min, "max": jnp.max}


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _check_geometry(op: str, cap: int) -> None:
    if op not in _FUSED_OPS:
        raise ValueError(f"fused accumulate needs a commutative op, got {op!r}")
    if cap <= 0 or cap % _LANES:
        raise ValueError(f"C-Buffer capacity must be a multiple of {_LANES}, got {cap}")


def _compiler_params(vmem_limit_bytes: Optional[int]):
    if vmem_limit_bytes is None:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit_bytes))


def _append(x, b, slot_ref, cb_off_ref, bin_range):
    """Store tuple ``x``'s bin-local offset at the bin's fill level and
    return that slot. One masked lane-row read-modify-write: a VMEM
    store at a dynamic lane does not exist on the TPU."""
    s = slot_ref[b]
    row = pl.ds(s // _LANES, 1)
    at = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) == s % _LANES
    cb_off_ref[b, row, :] = jnp.where(at, x - b * bin_range, cb_off_ref[b, row, :])
    return s, row, at


def _live_columns(cb_off_ref, b, fill):
    """The bin's buffered offsets turned onto sublanes: one (128, 1)
    column per C-Buffer lane row, with slots past ``fill`` set to -1 (a
    bin-local offset never equals -1)."""
    off_t = cb_off_ref[b].T  # (128, cap // 128)
    slot = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 1), 0)
    return [
        jnp.where(slot + c * _LANES < fill, off_t[:, c : c + 1], -1)
        for c in range(off_t.shape[1])
    ]


def _fused_kernel(
    idx_ref,
    val_ref,
    out_hbm,
    acc_ref,
    len_ref,
    cb_off_ref,
    cb_val_ref,
    sem,
    *,
    num_indices: int,
    num_bins: int,
    bin_range: int,
    cap: int,
    op: str,
):
    step = pl.program_id(0)
    drain_step = pl.num_programs(0) - 1
    ident = reduce_identity(op, acc_ref.dtype)
    combine, reduce = _COMBINE[op], _REDUCE[op]

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.full(acc_ref.shape, ident, acc_ref.dtype)

        def zero(b, carry):
            len_ref[b] = 0
            return carry

        jax.lax.fori_loop(0, num_bins, zero, 0)

    def flush_bin(b, fill):
        """Flush-by-reduction: reduce C-Buffer b into its accumulator
        rows. Accumulator row h holds bin-local offsets [128h, 128h+128);
        each buffered tuple column is compared against that row's lane
        offsets and reduced over sublanes — no HBM bin write happens."""
        offs = _live_columns(cb_off_ref, b, fill)
        vals_t = cb_val_ref[b].T
        vals = [vals_t[:, c : c + 1] for c in range(len(offs))]
        lanes = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)

        def row(h, carry):
            target = lanes + h * _LANES
            contrib = None
            for off, val in zip(offs, vals):
                part = reduce(jnp.where(off == target, val, ident), axis=0, keepdims=True)
                contrib = part if contrib is None else combine(contrib, part)
            acc_ref[b, pl.ds(h, 1), :] = combine(acc_ref[b, pl.ds(h, 1), :], contrib)
            return carry

        jax.lax.fori_loop(0, acc_ref.shape[1], row, 0)
        len_ref[b] = 0

    @pl.when(step < drain_step)
    def _process():
        def one(i, carry):
            x = idx_ref[i]

            @pl.when(jnp.logical_and(x >= 0, x < num_indices))
            def _():
                b = x // bin_range
                s, row, at = _append(x, b, len_ref, cb_off_ref, bin_range)
                cb_val_ref[b, row, :] = jnp.where(at, val_ref[i], cb_val_ref[b, row, :])
                len_ref[b] = s + 1

                @pl.when(s + 1 == cap)
                def _():
                    flush_bin(b, cap)

            return carry

        jax.lax.fori_loop(0, idx_ref.shape[0], one, 0)

    @pl.when(step == drain_step)
    def _drain():
        def drain(b, carry):
            fill = len_ref[b]

            @pl.when(fill > 0)
            def _():
                flush_bin(b, fill)

            return carry

        jax.lax.fori_loop(0, num_bins, drain, 0)
        copy = pltpu.make_async_copy(acc_ref, out_hbm, sem)
        copy.start()
        copy.wait()


def _fused_rows_kernel(
    idx_ref,
    val_ref,
    out_hbm,
    acc_ref,
    len_ref,
    cb_off_ref,
    cb_val_ref,
    sem,
    *,
    num_indices: int,
    num_bins: int,
    bin_range: int,
    cap: int,
    op: str,
):
    """Row-block fused bin-and-accumulate: the SpMM generalization.

    The accumulator is a ``(num_bins, bin_range, f_tile)`` C-Buffer tile
    over BOTH output vertices and feature columns; the grid is
    ``(n_ftiles, nblocks + 1)`` with the feature axis outermost (Pallas
    iterates the LAST grid dimension fastest), so one F-tile's
    accumulator stays VMEM-resident across the whole stream sweep and the
    index stream is re-streamed exactly ``F / f_tile`` times.

    Float ``add`` flushes are a ``(bin_range, 128) @ (128, f_tile)``
    one-hot matmul per C-Buffer lane row (MXU work); ``min``/``max`` and
    integer ``add`` (the MXU has no int32 matmul) reduce each
    accumulator row against the buffered tuples on the VPU.
    """
    ftile = pl.program_id(0)
    step = pl.program_id(1)
    drain_step = pl.num_programs(1) - 1
    dtype = acc_ref.dtype
    ident = reduce_identity(op, dtype)
    combine, reduce = _COMBINE[op], _REDUCE[op]
    matmul_flush = op == "add" and jnp.issubdtype(dtype, jnp.floating)

    @pl.when(step == 0)
    def _init():
        # re-entered once per F-tile: the accumulator and the C-Buffers
        # restart for the new feature columns
        acc_ref[...] = jnp.full(acc_ref.shape, ident, dtype)

        def zero(b, carry):
            len_ref[b] = 0
            return carry

        jax.lax.fori_loop(0, num_bins, zero, 0)

    def flush_bin(b, fill):
        chunks = cap // _LANES
        slot = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 1), 0)
        if matmul_flush:
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
            rows = jax.lax.broadcasted_iota(jnp.int32, (bin_range, _LANES), 0)
            offs = cb_off_ref[b]  # (chunks, 128)
            contrib = jnp.zeros(acc_ref.shape[1:], jnp.float32)
            for c in range(chunks):
                live = lane + c * _LANES < fill
                hit = jnp.logical_and(rows == offs[c : c + 1, :], live).astype(dtype)
                # unfilled slots hold stale values: select them to zero
                # BEFORE the dot (0 * garbage is NaN-unsafe for floats)
                vals = jnp.where(
                    slot + c * _LANES < fill,
                    cb_val_ref[b, pl.ds(c * _LANES, _LANES), :],
                    0,
                )
                contrib = contrib + jax.lax.dot(
                    hit, vals,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32,
                )
            acc_ref[b] = acc_ref[b] + contrib.astype(dtype)
        else:
            offs = _live_columns(cb_off_ref, b, fill)
            vals = [cb_val_ref[b, pl.ds(c * _LANES, _LANES), :] for c in range(chunks)]

            def row(r, carry):
                contrib = None
                for off, val in zip(offs, vals):
                    part = reduce(jnp.where(off == r, val, ident), axis=0, keepdims=True)
                    contrib = part if contrib is None else combine(contrib, part)
                acc_ref[b, pl.ds(r, 1), :] = combine(acc_ref[b, pl.ds(r, 1), :], contrib)
                return carry

            jax.lax.fori_loop(0, bin_range, row, 0)
        len_ref[b] = 0

    @pl.when(step < drain_step)
    def _process():
        def one(i, carry):
            x = idx_ref[i]

            @pl.when(jnp.logical_and(x >= 0, x < num_indices))
            def _():
                b = x // bin_range
                s, _, _ = _append(x, b, len_ref, cb_off_ref, bin_range)
                cb_val_ref[b, pl.ds(s, 1), :] = val_ref[pl.ds(i, 1), :]
                len_ref[b] = s + 1

                @pl.when(s + 1 == cap)
                def _():
                    flush_bin(b, cap)

            return carry

        jax.lax.fori_loop(0, idx_ref.shape[0], one, 0)

    @pl.when(step == drain_step)
    def _drain():
        def drain(b, carry):
            fill = len_ref[b]

            @pl.when(fill > 0)
            def _():
                flush_bin(b, fill)

            return carry

        jax.lax.fori_loop(0, num_bins, drain, 0)
        copy = pltpu.make_async_copy(acc_ref, out_hbm.at[ftile], sem)
        copy.start()
        copy.wait()


def _padded_indices(idx: jnp.ndarray, block: int):
    """Pad the index stream to whole blocks with -1 (dropped in-kernel)."""
    m = idx.shape[0]
    pad = (-m) % block
    return jnp.pad(idx.astype(jnp.int32), (0, pad), constant_values=-1), (m + pad) // block


def cobra_bin_accumulate_rows_pallas(
    idx: jnp.ndarray,
    val: jnp.ndarray,
    *,
    num_indices: int,
    bin_range: int = FUSED_BIN_RANGE,
    op: str = "add",
    block: int = FUSED_BLOCK,
    cap: int = FUSED_CAP,
    f_tile: Optional[int] = None,
    interpret: Optional[bool] = None,
    vmem_limit_bytes: Optional[int] = None,
) -> jnp.ndarray:
    """Fused row-block (SpMM) bin-and-accumulate in F/f_tile stream sweeps.

    ``val`` is ``(m, F)``; returns the dense ``(num_indices, F)``
    reduction with ``reduce_identity(op, val.dtype)`` at untouched rows.
    The feature axis is tiled at ``f_tile`` columns (default: all of F);
    each tile re-streams the index stream once, with a
    ``(num_bins, bin_range, f_tile)`` accumulator resident in VMEM for
    the whole sweep — the (V_tile × F_tile) C-Buffer of DESIGN.md §14.
    Compiled for a TPU, ``f_tile`` must be F or a multiple of 128.
    """
    _check_geometry(op, cap)
    if val.ndim != 2:
        raise ValueError(f"row-block accumulate wants (m, F) values, got {val.shape}")
    m, F = val.shape
    ident = reduce_identity(op, val.dtype)
    if m == 0 or F == 0:
        return jnp.full((num_indices, F), ident, val.dtype)
    ft = F if f_tile is None else int(f_tile)
    if not 1 <= ft <= F:
        raise ValueError(f"f_tile {ft} out of range for F={F}")
    num_bins = _cdiv(num_indices, bin_range)
    idx_p, nblocks = _padded_indices(idx, block)
    val_p = jnp.pad(val, ((0, idx_p.shape[0] - m), (0, (-F) % ft)))
    n_ftiles = val_p.shape[1] // ft

    def stream_map(f, i):
        return (jnp.minimum(i, nblocks - 1),)

    acc = pl.pallas_call(
        functools.partial(
            _fused_rows_kernel,
            num_indices=num_indices,
            num_bins=num_bins,
            bin_range=bin_range,
            cap=cap,
            op=op,
        ),
        # feature axis OUTERMOST: the whole stream is swept per F-tile,
        # plus one trailing drain step per tile
        grid=(n_ftiles, nblocks + 1),
        in_specs=[
            pl.BlockSpec((block,), stream_map, memory_space=pltpu.SMEM),
            pl.BlockSpec((block, ft), lambda f, i: (jnp.minimum(i, nblocks - 1), f)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n_ftiles, num_bins, bin_range, ft), val.dtype),
        scratch_shapes=[
            pltpu.VMEM((num_bins, bin_range, ft), val.dtype),  # accumulator
            pltpu.SMEM((num_bins,), jnp.int32),  # fill levels
            pltpu.VMEM((num_bins, cap // _LANES, _LANES), jnp.int32),  # C-Buffer offsets
            pltpu.VMEM((num_bins, cap, ft), val.dtype),  # C-Buffer row values
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=_compiler_params(vmem_limit_bytes),
        interpret=resolve_interpret(interpret),
    )(idx_p, val_p)
    acc = acc.transpose(1, 2, 0, 3).reshape(num_bins * bin_range, n_ftiles * ft)
    return acc[:num_indices, :F]


def cobra_bin_accumulate_pallas(
    idx: jnp.ndarray,
    val: jnp.ndarray,
    *,
    num_indices: int,
    bin_range: int = FUSED_BIN_RANGE,
    op: str = "add",
    block: int = FUSED_BLOCK,
    cap: int = FUSED_CAP,
    interpret: Optional[bool] = None,
    vmem_limit_bytes: Optional[int] = None,
) -> jnp.ndarray:
    """Fused bin-and-accumulate in ONE sweep of the (idx, val) stream.

    Returns the dense ``(num_indices,)`` reduction (``op`` in
    {"add", "min", "max"}) with ``reduce_identity(op, val.dtype)`` at untouched
    indices. Equivalent to ``kernels/ref.py::scatter_reduce_ref`` but the
    reordered tuple stream is never materialized in HBM: C-Buffer
    flushes reduce directly into the VMEM-resident accumulator. Values
    must be 32-bit (they stream through SMEM).
    """
    _check_geometry(op, cap)
    m = idx.shape[0]
    ident = reduce_identity(op, val.dtype)
    if m == 0:
        return jnp.full((num_indices,), ident, val.dtype)
    num_bins = _cdiv(num_indices, bin_range)
    rows = _cdiv(bin_range, _LANES)
    idx_p, nblocks = _padded_indices(idx, block)
    val_p = jnp.pad(val, (0, idx_p.shape[0] - m))

    def in_map(i):
        return (jnp.minimum(i, nblocks - 1),)

    acc = pl.pallas_call(
        functools.partial(
            _fused_kernel,
            num_indices=num_indices,
            num_bins=num_bins,
            bin_range=bin_range,
            cap=cap,
            op=op,
        ),
        grid=(nblocks + 1,),  # +1 drain step
        in_specs=[
            pl.BlockSpec((block,), in_map, memory_space=pltpu.SMEM),
            pl.BlockSpec((block,), in_map, memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((num_bins, rows, _LANES), val.dtype),
        scratch_shapes=[
            pltpu.VMEM((num_bins, rows, _LANES), val.dtype),  # accumulator
            pltpu.SMEM((num_bins,), jnp.int32),  # fill levels
            pltpu.VMEM((num_bins, cap // _LANES, _LANES), jnp.int32),  # C-Buffer offsets
            pltpu.VMEM((num_bins, cap // _LANES, _LANES), val.dtype),  # C-Buffer values
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=_compiler_params(vmem_limit_bytes),
        interpret=resolve_interpret(interpret),
    )(idx_p, val_p)
    acc = acc.reshape(num_bins, rows * _LANES)[:, :bin_range]
    return acc.reshape(num_bins * bin_range)[:num_indices]
