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

Given more than one device, each makes its own contiguous share of that
arc list (``generate``): the graph depends on the configuration and the
seed alone, not on the number of chips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.distributed_pb import STREAM_AXIS
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


def _quadrant_bits(u, t_a, t_b, t_c):
    """(src bit, dst bit) of each uint32 quadrant draw ``u``, cut at the
    ``quadrant_thresholds``."""
    src_bit = (u >= t_b).astype(jnp.int32)
    dst_bit = (((u >= t_a) & (u < t_b)) | (u >= t_c)).astype(jnp.int32)
    return src_bit, dst_bit


@functools.partial(jax.jit, static_argnames=("scale", "num_edges", "cuts"))
def kron_bits(key, scale: int, num_edges: int, cuts: tuple):
    """Kronecker endpoints before the vertex permutation: bit ``i`` of
    (src, dst) comes from the quadrant of draw ``i`` of each edge."""
    t_a, t_b, t_c = (jnp.uint32(t) for t in cuts)

    def bit(i, carry):
        src, dst = carry
        u = jax.random.bits(jax.random.fold_in(key, i), (num_edges,), jnp.uint32)
        src_bit, dst_bit = _quadrant_bits(u, t_a, t_b, t_c)
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


def _chip_arcs(key, scale: int, num_edges: int, cuts: tuple, per_chip: int):
    """Arcs ``[k * per_chip, (k + 1) * per_chip)`` of ``_kron``'s list on
    chip ``k`` of the stream axis, and no other. Arc ``i`` is tuple
    ``i mod num_edges``, reversed from ``num_edges`` on. Partitionable
    threefry draws element ``t`` of ``jax.random.bits(k, (num_edges,))``
    from the counter ``(0, t)`` alone, so each chip draws its tuples' bits
    without the others'."""
    k = jax.lax.axis_index(STREAM_AXIS).astype(jnp.uint32)
    i = k * jnp.uint32(per_chip) + jax.lax.iota(jnp.uint32, per_chip)
    rev = i >= jnp.uint32(num_edges)
    t = jnp.where(rev, i - jnp.uint32(num_edges), i)
    k_bits, k_perm = jax.random.split(key)
    t_a, t_b, t_c = (jnp.uint32(x) for x in cuts)

    def bit(b, carry):
        first, second = carry
        k1, k2 = jax.random.key_data(jax.random.fold_in(k_bits, b))
        h1, h2 = threefry2x32_p.bind(k1, k2, jnp.zeros_like(t), t)
        src_bit, dst_bit = _quadrant_bits(h1 ^ h2, t_a, t_b, t_c)
        first = first | (jnp.where(rev, dst_bit, src_bit) << b)
        second = second | (jnp.where(rev, src_bit, dst_bit) << b)
        return first, second

    zeros = jnp.zeros_like(t, jnp.int32)  # varies over the stream axis, as the carry does
    first, second = jax.lax.fori_loop(0, scale, bit, (zeros, zeros))
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    return jnp.take(perm, first), jnp.take(perm, second)


@functools.partial(jax.jit, static_argnames=("scale", "num_edges", "cuts", "mesh"))
def _kron_sharded(key, scale: int, num_edges: int, cuts: tuple, mesh: Mesh):
    per_chip = 2 * num_edges // mesh.size
    arcs = functools.partial(_chip_arcs, scale=scale, num_edges=num_edges, cuts=cuts, per_chip=per_chip)
    spec = P(STREAM_AXIS)
    return jax.shard_map(arcs, mesh=mesh, in_specs=P(), out_specs=(spec, spec))(key)


def generate(config: dict, key: jax.Array, devices=None) -> COO:
    """The configuration's arcs. With one device or ``None``, made on the
    default device. With more, ``src`` and ``dst`` are sharded over a
    one-axis mesh of ``devices`` (axis ``STREAM_AXIS``, which the
    program's sharded paths find): device ``k`` of ``c`` holds arcs
    ``[k A / c, (k + 1) A / c)`` of the ``A`` arcs, the same arcs in the
    same order as one device makes, and never the whole list."""
    n, m = int(config["num_nodes"]), int(config["num_edges"])
    kind = config["generator"]
    if kind != "kron":
        raise ValueError(f"unknown generator {kind!r}")
    scale = int(config["scale"])
    if n != 1 << scale or int(config["num_arcs"]) != 2 * m:
        raise ValueError(f"kron: want num_nodes 2**scale ({scale}) and num_arcs 2 x num_edges ({m})")
    cuts = quadrant_thresholds(config["a"], config["b"], config["c"])
    if devices is None or len(devices) == 1:
        src, dst = _kron(key, scale, m, cuts)
        return COO(src=src, dst=dst, num_nodes=n)
    chips = len(devices)
    if (2 * m) % chips:
        raise ValueError(f"kron: {2 * m} arcs do not divide over {chips} chips")
    if 2 * m > 1 << 32:
        raise ValueError(f"kron: {2 * m} arcs: the sharded generator counts arcs in 32 bits")
    if not jax.config.jax_threefry_partitionable:
        raise ValueError("kron: the sharded generator needs jax_threefry_partitionable")
    mesh = Mesh(np.asarray(devices), (STREAM_AXIS,))
    src, dst = _kron_sharded(key, scale, m, cuts, mesh)
    return COO(src=src, dst=dst, num_nodes=n)
