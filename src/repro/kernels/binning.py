"""Binning kernels — the paper's contribution as Pallas TPU kernels.

Two kernels, mirroring the paper's §3/§4 contrast:

``counting_positions``
    Software PB's Binning phase on TPU: a blocked pass that carries
    per-bin write cursors in VMEM scratch and emits each tuple's
    destination position. All math is dense (one-hot compare, cumsum,
    one-hot·cursor matmul = the gather), so the VPU/MXU run it without
    the scalar instruction overhead the paper identifies on CPUs — but
    like software PB it supports ONE bin range per pass.

``cobra_binning_pass``
    The COBRA kernel: per-bin C-Buffers live in VMEM scratch
    (``cb_idx/cb_val``: num_bins × cap tuples). Incoming blocks are
    appended to C-Buffers; a C-Buffer that would overflow is *flushed* —
    a coarse-grained, cacheline(tile)-sized sequential write to its HBM
    bin at the current cursor, exactly the eviction the paper's binning
    engines perform. A trailing grid step drains all buffers. The
    read-modify-write flush window is safe because TPU grids execute
    sequentially on a core.

Both are validated in interpret mode against ``ref.py`` and have no
Mosaic lowering (the in-block ranks use ``cumsum``; the appends are
per-tuple stores at dynamic lanes), so they raise on a TPU rather than
interpret there (``repro.kernels.interpret_only``). The fused kernels
(``kernels/fused.py``) show the shape that compiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_only


# ---------------------------------------------------------------------------
# Kernel 1: software-PB positions (single bin range per pass).
# ---------------------------------------------------------------------------


def _positions_kernel(keys_ref, starts_ref, pos_ref, cur_ref, *, num_bins: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        cur_ref[...] = starts_ref[...]

    keys = keys_ref[...]  # (block,)
    block = keys.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (block, num_bins), 1)
    onehot = (keys[:, None] == iota).astype(jnp.int32)  # (block, B)
    # stable in-block rank of each tuple among tuples of its bin
    ranks = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    # cursor gather expressed as one-hot reduction (MXU-friendly)
    base = jnp.sum(onehot * cur_ref[...][None, :], axis=1)
    pos_ref[...] = jnp.where(keys < num_bins, base + ranks, -1)
    cur_ref[...] += jnp.sum(onehot, axis=0)


def counting_positions_pallas(
    keys: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    num_bins: int,
    block: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Destination position of each element under a stable counting sort
    whose bin b region begins at starts[b]. Padding keys (== num_bins)
    map to -1."""
    m = keys.shape[0]
    pad = (-m) % block
    keys_p = jnp.pad(keys, (0, pad), constant_values=num_bins)
    grid = (keys_p.shape[0] // block,)
    pos = pl.pallas_call(
        functools.partial(_positions_kernel, num_bins=num_bins),
        grid=grid,
        name="pb_counting_positions",
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((num_bins,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((keys_p.shape[0],), jnp.int32),
        scratch_shapes=[pltpu.VMEM((num_bins,), jnp.int32)],
        interpret=interpret_only("counting_positions_pallas", interpret),
    )(keys_p, starts)
    return pos[:m]


# ---------------------------------------------------------------------------
# Kernel 2: COBRA — VMEM C-Buffers with flush-on-fill.
# ---------------------------------------------------------------------------


def _cobra_kernel(
    keys_ref,
    idx_ref,
    val_ref,
    starts_ref,
    out_idx_ref,
    out_val_ref,
    cur_ref,
    len_ref,
    cb_idx_ref,
    cb_val_ref,
    *,
    num_bins: int,
    cap: int,
    nblocks: int,
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        cur_ref[...] = starts_ref[...]
        len_ref[...] = jnp.zeros_like(len_ref)

    lane = jnp.arange(cap, dtype=jnp.int32)

    def flush_bin(b):
        """Coarse-grained eviction of C-Buffer b to its HBM bin region.
        Read-modify-write over a cap-sized window; positions beyond the
        buffer's fill level are written back unchanged."""
        l = len_ref[b]
        c = cur_ref[b]
        mask = lane < l
        window_i = out_idx_ref[pl.ds(c, cap)]
        window_v = out_val_ref[pl.ds(c, cap)]
        out_idx_ref[pl.ds(c, cap)] = jnp.where(mask, cb_idx_ref[b, :], window_i)
        out_val_ref[pl.ds(c, cap)] = jnp.where(mask, cb_val_ref[b, :], window_v)
        cur_ref[b] = c + l
        len_ref[b] = 0

    @pl.when(step < nblocks)
    def _process():
        keys = keys_ref[...]
        idx = idx_ref[...]
        val = val_ref[...]
        block = keys.shape[0]
        iota = jax.lax.broadcasted_iota(jnp.int32, (block, num_bins), 1)
        onehot = (keys[:, None] == iota).astype(jnp.int32)
        incoming = jnp.sum(onehot, axis=0)  # (B,)
        ranks = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1

        # 1) evict any C-Buffer the incoming block would overflow
        need = jnp.logical_and(len_ref[...] + incoming > cap, len_ref[...] > 0)

        def maybe_flush(b, _):
            jax.lax.cond(need[b], lambda: flush_bin(b), lambda: None)
            return 0

        jax.lax.fori_loop(0, num_bins, maybe_flush, 0)

        # 2) append the block's tuples into their C-Buffers
        lens_now = len_ref[...]

        def append(i, _):
            k = keys[i]

            def do():
                slot = lens_now[k] + ranks[i]
                cb_idx_ref[k, slot] = idx[i]
                cb_val_ref[k, slot] = val[i]

            jax.lax.cond(k < num_bins, do, lambda: None)
            return 0

        jax.lax.fori_loop(0, block, append, 0)
        len_ref[...] = lens_now + incoming

    @pl.when(step == nblocks)
    def _drain():
        def drain(b, _):
            flush_bin(b)
            return 0

        jax.lax.fori_loop(0, num_bins, drain, 0)


def cobra_binning_pass_pallas(
    keys: jnp.ndarray,
    idx: jnp.ndarray,
    val: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    num_bins: int,
    block: int = 512,
    cap: int = 512,
    interpret: Optional[bool] = None,
):
    """One COBRA binning pass. keys[i] = bin of tuple (idx[i], val[i]);
    starts (num_bins,) = exclusive bin starts. Returns binned (idx, val),
    stable within each bin."""
    assert cap >= block, "C-Buffer capacity must cover one block"
    m = keys.shape[0]
    pad = (-m) % block
    keys_p = jnp.pad(keys, (0, pad), constant_values=num_bins)
    idx_p = jnp.pad(idx, (0, pad))
    val_p = jnp.pad(val, (0, pad))
    nblocks = keys_p.shape[0] // block
    m_out = m + cap  # flush windows may overhang by < cap
    grid = (nblocks + 1,)  # +1 drain step

    def in_map(i):
        return (jnp.minimum(i, nblocks - 1),)

    out_idx, out_val = pl.pallas_call(
        functools.partial(_cobra_kernel, num_bins=num_bins, cap=cap, nblocks=nblocks),
        grid=grid,
        name="pb_cobra_binning_pass",
        in_specs=[
            pl.BlockSpec((block,), in_map),
            pl.BlockSpec((block,), in_map),
            pl.BlockSpec((block,), in_map),
            pl.BlockSpec((num_bins,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((m_out,), lambda i: (0,)),
            pl.BlockSpec((m_out,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m_out,), jnp.int32),
            jax.ShapeDtypeStruct((m_out,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((num_bins,), jnp.int32),  # cursors (SMEM on real TPU)
            pltpu.VMEM((num_bins,), jnp.int32),  # fill levels
            pltpu.VMEM((num_bins, cap), jnp.int32),  # C-Buffer idx
            pltpu.VMEM((num_bins, cap), jnp.int32),  # C-Buffer val
        ],
        interpret=interpret_only("cobra_binning_pass_pallas", interpret),
    )(keys_p, idx_p, val_p, starts)
    return out_idx[:m], out_val[:m]
