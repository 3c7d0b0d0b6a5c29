"""Histogram kernel — counting via one-hot reduction (MXU-native).

PB's Binning needs per-bin counts to lay bins out contiguously. On a
multicore this is scalar increments (random access); on TPU, counting is
a rank-1 reduction: build the (block, num_bins) one-hot occupancy tile in
VMEM and reduce over the block axis. The reduction is expressible as a
matmul with a ones-vector, which the MXU executes at full throughput —
this is the "hardware-assisted" histogram of the COBRA adaptation
(DESIGN.md §2, assumption change 3).

Grid: one step per key block; the single output block is accumulated
across steps (TPU grids execute sequentially on a core, so read-modify-
write of the same output block is well-defined).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _histogram_kernel(keys_ref, out_ref, *, num_bins: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]  # (block,)
    iota = jax.lax.broadcasted_iota(jnp.int32, (keys.shape[0], num_bins), 1)
    onehot = (keys[:, None] == iota).astype(jnp.int32)  # (block, B) in VMEM
    out_ref[...] += jnp.sum(onehot, axis=0)


def histogram_pallas(
    keys: jnp.ndarray, num_bins: int, *, block: int = 2048, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """Count occurrences of each value in [0, num_bins). Out-of-range keys
    (e.g. padding = num_bins) are ignored."""
    m = keys.shape[0]
    pad = (-m) % block
    keys_p = jnp.pad(keys, (0, pad), constant_values=num_bins)
    grid = (keys_p.shape[0] // block,)
    return pl.pallas_call(
        functools.partial(_histogram_kernel, num_bins=num_bins),
        grid=grid,
        name="pb_histogram",
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((num_bins,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((num_bins,), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(keys_p)
