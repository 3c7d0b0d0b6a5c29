"""Graph generator of the benchmark, drawn on the device from a seed.

``generate`` takes a configuration (``bench/configs/<name>.json``) and a
``jax.random`` key, and returns ``repro.core.graph.COO`` with int32
endpoints. The same seed gives the same edge list, on any backend.

  ``kron`` — Graph500 Kronecker (A/B/C = 0.57/0.19/0.19): one quadrant
             draw per scale bit and edge tuple, then a seeded permutation
             of the vertex ids. The distribution of ``core.graph.gen_kron``
             (host numpy), not its numbers. Graph500's graph is
             undirected, and its edge list keeps self loops and repeated
             tuples, so the program gets both directions of every tuple:
             the ``num_edges`` tuples forward, then the same tuples
             reversed, ``2 * num_edges`` arcs in all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import COO


def seed_key(seed: int) -> jax.Array:
    """A threefry key from all 64 bits of ``seed``: ``jax.random.key``
    keeps only the low 32 bits of a Python int when x64 is off, so seeds
    2**32 apart would give one graph."""
    seed %= 1 << 64
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def quadrant_thresholds(a: float, b: float, c: float) -> tuple:
    """uint32 cut points of one quadrant draw: a draw below the first is
    quadrant A (no bit set), below the second B (dst bit), below the third
    C (src bit), else D (both)."""
    cuts = np.cumsum([a, b, c]) * 2.0**32
    return tuple(int(round(x)) for x in cuts)


@functools.partial(jax.jit, static_argnames=("scale", "num_edges", "cuts"))
def kron_bits(key, scale: int, num_edges: int, cuts: tuple):
    """Kronecker endpoints before the vertex permutation: bit ``i`` of
    (src, dst) comes from the quadrant of draw ``i`` of each edge."""
    t_a, t_b, t_c = (jnp.uint32(t) for t in cuts)

    def bit(i, carry):
        src, dst = carry
        u = jax.random.bits(jax.random.fold_in(key, i), (num_edges,), jnp.uint32)
        src_bit = (u >= t_b).astype(jnp.int32)
        dst_bit = (((u >= t_a) & (u < t_b)) | (u >= t_c)).astype(jnp.int32)
        return src | (src_bit << i), dst | (dst_bit << i)

    zeros = jnp.zeros((num_edges,), jnp.int32)
    return jax.lax.fori_loop(0, scale, bit, (zeros, zeros))


@functools.partial(jax.jit, static_argnames=("scale", "num_edges", "cuts"))
def _kron(key, scale: int, num_edges: int, cuts: tuple):
    k_bits, k_perm = jax.random.split(key)
    src, dst = kron_bits(k_bits, scale, num_edges, cuts)
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    src, dst = jnp.take(perm, src), jnp.take(perm, dst)
    return jnp.concatenate([src, dst]), jnp.concatenate([dst, src])


def generate(config: dict, key: jax.Array) -> COO:
    """The configuration's arcs, made on the default device."""
    n, m = int(config["num_nodes"]), int(config["num_edges"])
    kind = config["generator"]
    if kind != "kron":
        raise ValueError(f"unknown generator {kind!r}")
    scale = int(config["scale"])
    if n != 1 << scale or int(config["num_arcs"]) != 2 * m:
        raise ValueError(f"kron: want num_nodes 2**scale ({scale}) and num_arcs 2 x num_edges ({m})")
    cuts = quadrant_thresholds(config["a"], config["b"], config["c"])
    src, dst = _kron(key, scale, m, cuts)
    return COO(src=src, dst=dst, num_nodes=n)
