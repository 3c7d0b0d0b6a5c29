"""Bin-Read kernel — per-bin commutative apply with the working set in VMEM.

Bin b owns index range [b*R, (b+1)*R). Its tuples are presented as a
padded (L,) tile; the kernel builds the (L, R) one-hot of local indices
and reduces updates with a single (R, L) @ (L, d) matmul — the MXU does
the scatter-add. Duplicate indices within the bin coalesce *inside the
matmul*: this realizes the PHI-style in-cache update coalescing the
paper cites (§7) as composable with COBRA, for free on a systolic array.

The output block (R, d) is written once per grid step — the bin's whole
index range is VMEM-resident, which is precisely Bin-Read's locality
condition (paper Fig. 3, right).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_only


def _binread_kernel(idx_ref, val_ref, out_ref, *, bin_range: int):
    b = pl.program_id(0)
    idx = idx_ref[0, :]  # (L,) global indices of this bin's tuples (-1 pad)
    val = val_ref[0, :, :]  # (L, d)
    local = idx - b * bin_range  # in [0, R) for real tuples
    L = idx.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (L, bin_range), 1)
    onehot = (local[:, None] == iota).astype(val.dtype)  # (L, R); pads match nothing
    out_ref[...] = jnp.dot(
        onehot.T, val, preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


def binread_scatter_add_pallas(
    idx_padded: jnp.ndarray,  # (B, L) int32, -1 padding
    val_padded: jnp.ndarray,  # (B, L, d)
    *,
    bin_range: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Returns (B*bin_range, d): accumulation of val rows at their indices."""
    B, L = idx_padded.shape
    d = val_padded.shape[-1]
    return pl.pallas_call(
        functools.partial(_binread_kernel, bin_range=bin_range),
        grid=(B,),
        name="pb_binread_scatter_add",
        in_specs=[
            pl.BlockSpec((1, L), lambda b: (b, 0)),
            pl.BlockSpec((1, L, d), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bin_range, d), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B * bin_range, d), val_padded.dtype),
        interpret=interpret_only("binread_scatter_add_pallas", interpret),
    )(idx_padded, val_padded)
