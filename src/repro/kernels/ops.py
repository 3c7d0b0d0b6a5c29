"""Jitted wrappers composing the Pallas kernels into framework ops.

``interpret=None`` everywhere resolves through
``repro.kernels.resolve_interpret``: interpret mode off TPU, compiled
Mosaic on a TPU. The histogram kernel compiles for the chip; the
positions, C-Buffer binning pass, Bin-Read and row-scatter kernels have
no Mosaic lowering and raise on a TPU instead of interpreting.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import pb as pb_core
from repro.core.plan import CobraPlan
from repro.kernels.binning import cobra_binning_pass_pallas, counting_positions_pallas
from repro.kernels.binread import binread_scatter_add_pallas
from repro.kernels.histogram import histogram_pallas
from repro.kernels.scatter_rows import scatter_rows_pallas


@functools.partial(jax.jit, static_argnames=("num_bins", "block", "interpret"))
def histogram(keys, num_bins: int, block: int = 2048, interpret: Optional[bool] = None):
    return histogram_pallas(keys, num_bins, block=block, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("bin_range", "num_bins", "block", "interpret")
)
def pb_binning(
    idx, val, *, bin_range: int, num_bins: int, block: int = 1024, interpret: Optional[bool] = None
) -> pb_core.Bins:
    """Software-PB binning built from the Pallas histogram + positions
    kernels; the permutation apply is an XLA scatter."""
    keys = (idx // bin_range).astype(jnp.int32)
    counts = histogram_pallas(keys, num_bins, block=block, interpret=interpret)
    starts = pb_core.starts_from_counts(counts)
    pos = counting_positions_pallas(
        keys, starts[:-1], num_bins=num_bins, block=block, interpret=interpret
    )
    m = idx.shape[0]
    out_idx = jnp.zeros((m,), idx.dtype).at[pos].set(idx)
    out_val = jnp.zeros((m,), val.dtype).at[pos].set(val)
    return pb_core.Bins(idx=out_idx, val=out_val, starts=starts, bin_range=bin_range)


@functools.partial(
    jax.jit, static_argnames=("bin_range", "num_bins", "block", "cap", "interpret")
)
def cobra_binning_pass(
    idx,
    val,
    *,
    bin_range: int,
    num_bins: int,
    block: int = 512,
    cap: int = 512,
    interpret: Optional[bool] = None,
) -> pb_core.Bins:
    """One COBRA C-Buffer pass (histogram + flush-managed binning)."""
    keys = (idx // bin_range).astype(jnp.int32)
    counts = histogram_pallas(keys, num_bins, block=block, interpret=interpret)
    starts = pb_core.starts_from_counts(counts)
    out_idx, out_val = cobra_binning_pass_pallas(
        keys,
        idx,
        val,
        starts[:-1],
        num_bins=num_bins,
        block=block,
        cap=cap,
        interpret=interpret,
    )
    return pb_core.Bins(idx=out_idx, val=out_val, starts=starts, bin_range=bin_range)


def cobra_binning(
    idx,
    val,
    plan: CobraPlan,
    *,
    block: int = 512,
    cap: int = 512,
    max_bins_per_pass: int = 4096,
    interpret: Optional[bool] = None,
) -> pb_core.Bins:
    """Hierarchical COBRA binning: one C-Buffer pass per plan level
    (coarse -> fine), the TPU realization of the paper's multi-level
    C-Buffer hierarchy (DESIGN.md §2)."""
    n = plan.num_indices
    out = None
    for rng in plan.level_ranges():
        nb = -(-n // rng)
        if nb > max_bins_per_pass:
            raise ValueError(
                f"pass at range {rng} needs {nb} bins > {max_bins_per_pass}; "
                "use a plan with fewer levels or larger final range"
            )
        out = cobra_binning_pass(
            idx, val, bin_range=rng, num_bins=nb, block=block, cap=cap, interpret=interpret
        )
        idx, val = out.idx, out.val
    assert out is not None
    return out


@functools.partial(jax.jit, static_argnames=("max_per_bin", "num_bins"))
def padded_bin_layout(bins: pb_core.Bins, num_bins: int, max_per_bin: int):
    """Compact binned stream -> (B, L) padded layout for the Bin-Read
    kernel. Bins longer than max_per_bin are truncated (callers size L
    from the histogram)."""
    B, L = num_bins, max_per_bin
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    cols = jnp.arange(L, dtype=jnp.int32)[None, :]
    src = bins.starts[:-1][:, None] + cols
    valid = cols < (bins.starts[1:] - bins.starts[:-1])[:, None]
    m = bins.idx.shape[0]
    src = jnp.clip(src, 0, m - 1)
    idx_p = jnp.where(valid, jnp.take(bins.idx, src), -1)
    val_p = jnp.where(valid[..., None] if bins.val.ndim > 1 else valid,
                      jnp.take(bins.val, src, axis=0), 0)
    del rows
    return idx_p, val_p


@functools.partial(jax.jit, static_argnames=("bin_range", "interpret"))
def binread_scatter_add(idx_padded, val_padded, *, bin_range: int, interpret: Optional[bool] = None):
    return binread_scatter_add_pallas(
        idx_padded, val_padded, bin_range=bin_range, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("out_rows", "block", "interpret"))
def scatter_rows(x, pos, out_rows: int, block: int = 256, interpret: Optional[bool] = None):
    return scatter_rows_pallas(x, pos, out_rows, block=block, interpret=interpret)


def pb_scatter_add_full(
    idx,
    updates,  # (m, d)
    out_size: int,
    *,
    bin_range: int,
    block: int = 1024,
    interpret: Optional[bool] = None,
):
    """End-to-end PB scatter-add through the kernels: histogram ->
    positions -> row permute -> per-bin MXU apply. Used by the embedding
    backward integration and its benchmarks. Non-jittable at the top
    level (L is data-dependent); callers jit per (shape, L) bucket."""
    num_bins = -(-out_size // bin_range)
    keys = (idx // bin_range).astype(jnp.int32)
    counts = histogram(keys, num_bins, block=block, interpret=interpret)
    starts = pb_core.starts_from_counts(counts)
    pos = counting_positions_pallas(
        keys, starts[:-1], num_bins=num_bins, block=block, interpret=interpret
    )
    binned_idx = jnp.zeros_like(idx).at[pos].set(idx)
    binned_upd = scatter_rows(updates, pos, idx.shape[0], block=block, interpret=interpret)
    L = int(jnp.max(counts))  # host sync: sizes the padded layout
    L = max(8, -(-L // 8) * 8)
    bins = pb_core.Bins(binned_idx, binned_upd, starts, bin_range)
    idx_p, val_p = padded_bin_layout(bins, num_bins, L)
    out = binread_scatter_add(idx_p, val_p, bin_range=bin_range, interpret=interpret)
    return out[:out_size]
