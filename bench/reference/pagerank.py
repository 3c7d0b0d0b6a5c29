"""Plain PageRank reference: float64 power iteration in numpy.

The semantics of ``repro.core.pagerank``'s push iteration, written out
independently of it: out-degree clamped to 1 (a dangling vertex's mass
is dropped), uniform start and teleport, damping 0.85, a fixed number of
iterations. Nothing of the program is imported.

``ranks_bf16`` is the control: the same iteration computed in bfloat16,
the precision below the float32 the configuration states, on the device.
A comparison that cannot tell it from the program is no comparison.
"""
from __future__ import annotations

import functools

import numpy as np

DAMP = 0.85


def ranks(src: np.ndarray, dst: np.ndarray, num_nodes: int, iters: int) -> np.ndarray:
    """float64 ranks after ``iters`` power iterations."""
    n = num_nodes
    inv_out = 1.0 / np.maximum(np.bincount(src, minlength=n), 1).astype(np.float64)
    r = np.full(n, 1.0 / n)
    for _ in range(iters):
        incoming = np.bincount(dst, weights=(r * inv_out)[src], minlength=n)
        r = (1.0 - DAMP) / n + DAMP * incoming
    return r


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Widest relative gap over the vertices. Every reference rank is at
    least the teleport share (1 - DAMP) / n, so no division is by zero;
    a result of the wrong length is off by 1 everywhere."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        return float("inf")
    with np.errstate(invalid="ignore"):
        err = np.abs(got - want) / want
    return float(np.nanmax(np.where(np.isfinite(err), err, np.inf)))


@functools.lru_cache(maxsize=None)
def _bf16_fn(num_nodes: int, iters: int):
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    n = num_nodes

    def run(src, dst):
        outdeg = jnp.maximum(jnp.bincount(src, length=n), 1).astype(bf)
        r = jnp.full((n,), 1.0 / n, bf)
        for _ in range(iters):
            contrib = (r / outdeg).astype(bf)
            incoming = jnp.zeros((n,), bf).at[dst].add(jnp.take(contrib, src))
            r = (bf((1.0 - DAMP) / n) + bf(DAMP) * incoming).astype(bf)
        return r

    return jax.jit(run)


def ranks_bf16(src, dst, num_nodes: int, iters: int) -> np.ndarray:
    """The control: the reference iteration in bfloat16 on the device."""
    out = _bf16_fn(num_nodes, iters)(src, dst)
    return np.asarray(out.astype("float32"), np.float64)
