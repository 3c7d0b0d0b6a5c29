"""Row-scatter kernel — the permute-apply of PB dispatch for vector payloads.

MoE dispatch (and any binned layout change of row data) needs
``out[pos[i], :] = x[i, :]`` where ``pos`` is the destination computed by
the binning kernels. Rows are d-wide vectors, so each store is a full
VREG-line copy (the coalesced transfer unit), not a scalar scatter.

Grid: one step per row block. The output is addressed as a whole ref
(positions are data-dependent); TPU grids are sequential so the
disjoint-position writes are well-defined.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_only


def _scatter_rows_kernel(pos_ref, x_ref, out_ref):
    pos = pos_ref[...]  # (K,)
    x = x_ref[...]  # (K, d)
    K = pos.shape[0]

    def body(i, _):
        p = pos[i]

        def do():
            row = jax.lax.dynamic_slice_in_dim(x, i, 1, axis=0)
            out_ref[pl.ds(p, 1), :] = row

        jax.lax.cond(p >= 0, do, lambda: None)
        return 0

    jax.lax.fori_loop(0, K, body, 0)


def scatter_rows_pallas(
    x: jnp.ndarray,  # (m, d)
    pos: jnp.ndarray,  # (m,) destination row of each input row; -1 = drop
    out_rows: int,
    *,
    block: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """out[pos[i]] = x[i]; unwritten rows are zero."""
    m, d = x.shape
    pad = (-m) % block
    x_p = jnp.pad(x, ((0, pad), (0, 0)))
    pos_p = jnp.pad(pos, (0, pad), constant_values=-1)
    nblocks = x_p.shape[0] // block
    # zero-init by writing through an explicit zeros input alias
    zeros = jnp.zeros((out_rows, d), x.dtype)

    def kernel(pos_ref, x_ref, init_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[...] = init_ref[...]

        _scatter_rows_kernel(pos_ref, x_ref, out_ref)

    return pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((out_rows, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((out_rows, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows, d), x.dtype),
        interpret=interpret_only("scatter_rows_pallas", interpret),
    )(pos_p, x_p, zeros)
