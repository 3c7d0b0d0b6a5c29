"""Graph containers and synthetic generators.

The paper evaluates on 5 large graphs (DBP, KRON, URND, EURO, HBUBL) that
are diverse in degree distribution (power-law / normal / bounded-degree).
We provide seeded synthetic analogues of each family so the benchmark
suite reproduces the *structure* of the paper's tables without shipping
multi-GB inputs.

Representations (paper Fig. 1, plus the mutation layout of DESIGN.md §15):
  COO      — "Edgelist": parallel (src, dst) arrays, arbitrary edge order.
  CSR      — offsets (n+1) + neighbor array sorted by src.
  CSC      — CSR of the transposed graph (in-neighbors), used by pull kernels.
  SlackCSR — CSR with per-vertex capacity slack: each vertex owns a slab
             larger than its degree, so edge insertions append in place
             and deletions tombstone in place (``core/updates.py``).
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np


class COO(NamedTuple):
    """Edgelist. src/dst are int32 arrays of equal length (num_edges)."""

    src: jnp.ndarray
    dst: jnp.ndarray
    num_nodes: int

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])


class CSR(NamedTuple):
    """Compressed sparse row. offsets has length num_nodes+1."""

    offsets: jnp.ndarray
    neighs: jnp.ndarray
    num_nodes: int

    @property
    def num_edges(self) -> int:
        return int(self.neighs.shape[0])


# Sentinel neighbor id marking a deleted (tombstoned) slot in a SlackCSR
# slab. -1 is outside every valid vertex id, so a live-slot test is a
# single compare and never collides with real edges.
TOMBSTONE = -1


class SlackCSR(NamedTuple):
    """Capacity-slack CSR: the mutable layout (DESIGN.md §15).

    Each vertex v owns the slab ``neighs[offsets[v] : offsets[v+1]]``
    whose capacity exceeds its degree by a headroom factor. The first
    ``counts[v]`` slots are OCCUPIED (in insertion order); an occupied
    slot holding ``TOMBSTONE`` is a deleted edge awaiting compaction;
    slots past ``counts[v]`` are free slack. Insertions append at
    ``offsets[v] + counts[v]``; deletions tombstone in place — both are
    O(batch) scatters, never a full rebuild. Tombstones consume slack
    until ``to_csr()`` (or the rebuild path in ``core/updates.py``)
    compacts them, which is what makes the slack-exhaustion rebuild
    threshold meaningful.
    """

    offsets: jnp.ndarray  # (n+1,) slab starts: capacity prefix sum
    neighs: jnp.ndarray  # (capacity,) slot values; TOMBSTONE = deleted
    counts: jnp.ndarray  # (n,) occupied slots per slab (live + tombstoned)
    num_nodes: int

    @property
    def capacity(self) -> int:
        return int(self.neighs.shape[0])

    @property
    def num_occupied(self) -> int:
        return int(np.asarray(self.counts).sum())

    @property
    def num_edges(self) -> int:
        """Live (non-tombstoned) edges."""
        return int(np.asarray(self.live_degrees()).sum())

    @property
    def slack_fraction(self) -> float:
        """Free slots / capacity — the rebuild-threshold quantity."""
        cap = self.capacity
        if cap == 0:
            return 1.0
        return 1.0 - self.num_occupied / cap

    def _slot_masks(self):
        """(slot -> vertex, occupied mask, live mask) on host."""
        off = np.asarray(self.offsets)
        nei = np.asarray(self.neighs)
        cnt = np.asarray(self.counts)
        seg = np.repeat(np.arange(self.num_nodes), np.diff(off))
        r = np.arange(nei.shape[0]) - off[seg]
        occupied = r < cnt[seg]
        return seg, occupied, occupied & (nei != TOMBSTONE)

    def live_degrees(self) -> jnp.ndarray:
        """(n,) live out-degree (occupied minus tombstoned)."""
        seg, _, live = self._slot_masks()
        return jnp.asarray(
            np.bincount(seg[live], minlength=self.num_nodes).astype(np.int32)
        )

    @classmethod
    def from_csr(
        cls, csr: CSR, *, headroom: float = 0.25, min_slack: int = 4
    ) -> "SlackCSR":
        """Slack layout of ``csr``: per-vertex capacity = degree plus
        ``max(min_slack, ceil(degree * headroom))``, slot order preserved
        — so ``from_csr(c).to_csr()`` reproduces ``c`` exactly."""
        if headroom < 0 or min_slack < 0:
            raise ValueError(
                f"headroom/min_slack must be >= 0, got {headroom}/{min_slack}"
            )
        off = np.asarray(csr.offsets).astype(np.int64)
        nei = np.asarray(csr.neighs)
        deg = np.diff(off)
        cap = deg + np.maximum(min_slack, np.ceil(deg * headroom).astype(np.int64))
        soff = np.concatenate([[0], np.cumsum(cap)])
        slab = np.full(int(soff[-1]), TOMBSTONE, np.int32)
        seg = np.repeat(np.arange(csr.num_nodes), cap)
        r = np.arange(slab.shape[0]) - soff[seg]
        occ = r < deg[seg]
        slab[occ] = nei[(off[seg] + r)[occ]]
        return cls(
            offsets=jnp.asarray(soff.astype(np.int32)),
            neighs=jnp.asarray(slab),
            counts=jnp.asarray(deg.astype(np.int32)),
            num_nodes=csr.num_nodes,
        )

    def to_csr(self) -> CSR:
        """Compact to an exact CSR: drop tombstones and free slack,
        preserving per-vertex slot order."""
        nei = np.asarray(self.neighs)
        seg, _, live = self._slot_masks()
        deg = np.bincount(seg[live], minlength=self.num_nodes)
        return CSR(
            offsets=jnp.asarray(
                np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
            ),
            neighs=jnp.asarray(nei[live].astype(np.int32)),
            num_nodes=self.num_nodes,
        )

    def to_coo(self) -> COO:
        """Live edges as an Edgelist (CSR slot order) — the rebuild
        path's input to ``PreprocessPipeline``."""
        nei = np.asarray(self.neighs)
        seg, _, live = self._slot_masks()
        return COO(
            src=jnp.asarray(seg[live].astype(np.int32)),
            dst=jnp.asarray(nei[live].astype(np.int32)),
            num_nodes=self.num_nodes,
        )


def degrees_from_coo(coo: COO, *, by: str = "src") -> jnp.ndarray:
    key = coo.src if by == "src" else coo.dst
    return jnp.bincount(key, length=coo.num_nodes).astype(jnp.int32)


def offsets_from_degrees(degrees: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix sum with a trailing total: shape (n+1,)."""
    z = jnp.zeros((1,), dtype=jnp.int32)
    return jnp.concatenate([z, jnp.cumsum(degrees, dtype=jnp.int32)])


def segment_ids_from_offsets(offsets: jnp.ndarray, num_edges: int) -> jnp.ndarray:
    """Edge -> owning row, given CSR offsets. Vectorized `repeat`."""
    return (
        jnp.searchsorted(
            offsets[1:], jnp.arange(num_edges, dtype=jnp.int32), side="right"
        )
    ).astype(jnp.int32)


def transpose_coo(coo: COO) -> COO:
    return COO(src=coo.dst, dst=coo.src, num_nodes=coo.num_nodes)


# ---------------------------------------------------------------------------
# Synthetic generators (numpy on host; deterministic by seed).
# ---------------------------------------------------------------------------


def _to_coo(src: np.ndarray, dst: np.ndarray, n: int) -> COO:
    return COO(
        src=jnp.asarray(src, dtype=jnp.int32),
        dst=jnp.asarray(dst, dtype=jnp.int32),
        num_nodes=int(n),
    )


def gen_uniform(num_nodes: int, avg_degree: int, seed: int = 0) -> COO:
    """URND analogue: uniform random endpoints (normal degree dist)."""
    rng = np.random.default_rng(seed)
    m = num_nodes * avg_degree
    src = rng.integers(0, num_nodes, size=m, dtype=np.int32)
    dst = rng.integers(0, num_nodes, size=m, dtype=np.int32)
    return _to_coo(src, dst, num_nodes)


def gen_kron(scale: int, avg_degree: int, seed: int = 0) -> COO:
    """KRON analogue: RMAT/Kronecker with Graph500 parameters."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * avg_degree
    a, b, c = 0.57, 0.19, 0.19
    # P(dst bit set | src bit clear), P(dst bit set | src bit set)
    p_right_dst = (a / (a + b), c / (c + (1 - a - b - c)))
    src = np.zeros(m, dtype=np.int32)
    dst = np.zeros(m, dtype=np.int32)
    r = np.empty(m)  # one draw buffer, refilled in place (same stream)
    for bit in range(scale):
        rng.random(out=r)
        # quadrant choice per RMAT
        go_right_src = r >= a + b  # bottom half -> src bit set
        rng.random(out=r)
        go_right_dst = np.where(go_right_src, r >= p_right_dst[1], r >= p_right_dst[0])
        src |= np.left_shift(go_right_src, bit, dtype=np.int32)
        dst |= np.left_shift(go_right_dst, bit, dtype=np.int32)
    perm = rng.permutation(n).astype(np.int32)  # avoid locality from bit construction
    return _to_coo(perm[src], perm[dst], n)


def gen_powerlaw(num_nodes: int, avg_degree: int, seed: int = 0, alpha: float = 1.8) -> COO:
    """DBP analogue: Zipf-distributed destination popularity."""
    rng = np.random.default_rng(seed)
    m = num_nodes * avg_degree
    ranks = np.arange(1, num_nodes + 1, dtype=np.float64)
    probs = ranks ** (-alpha)
    probs /= probs.sum()
    perm = rng.permutation(num_nodes).astype(np.int32)
    dst = perm[rng.choice(num_nodes, size=m, p=probs)]
    src = rng.integers(0, num_nodes, size=m, dtype=np.int32)
    return _to_coo(src, dst, num_nodes)


def gen_road(side: int, seed: int = 0) -> COO:
    """EURO analogue: 2D grid (bounded degree ~4), ids shuffled so the
    Edgelist has no inherent locality (as a downloaded edgelist would)."""
    rng = np.random.default_rng(seed)
    n = side * side
    ii, jj = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    vid = (ii * side + jj).astype(np.int64)
    edges = []
    right = vid[:, :-1].ravel(), vid[:, 1:].ravel()
    down = vid[:-1, :].ravel(), vid[1:, :].ravel()
    for s, d in (right, down):
        edges.append((s, d))
        edges.append((d, s))
    src = np.concatenate([e[0] for e in edges])
    dst = np.concatenate([e[1] for e in edges])
    perm = rng.permutation(n)
    order = rng.permutation(src.shape[0])  # shuffle edge order too
    return _to_coo(perm[src][order].astype(np.int32), perm[dst][order].astype(np.int32), n)


def gen_bubbles(side: int, seed: int = 0) -> COO:
    """HBUBL analogue: triangulated mesh (degree ~3) — grid + one diagonal."""
    rng = np.random.default_rng(seed)
    n = side * side
    ii, jj = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    vid = (ii * side + jj).astype(np.int64)
    pairs = [
        (vid[:, :-1].ravel(), vid[:, 1:].ravel()),
        (vid[:-1, :].ravel(), vid[1:, :].ravel()),
        (vid[:-1, :-1].ravel(), vid[1:, 1:].ravel()),
    ]
    src = np.concatenate([p[0] for p in pairs] + [p[1] for p in pairs])
    dst = np.concatenate([p[1] for p in pairs] + [p[0] for p in pairs])
    perm = rng.permutation(n)
    order = rng.permutation(src.shape[0])
    return _to_coo(perm[src][order].astype(np.int32), perm[dst][order].astype(np.int32), n)


# Version of the generators + npz layout above. Bump on ANY change to a
# generator's sampling logic or to the cache schema: the version is part
# of every cache entry, so stale files regenerate instead of silently
# deserializing a graph the current code would never produce.
GRAPH_GEN_VERSION = 2


def _graph_cache_dir() -> str:
    import os

    base = os.environ.get("REPRO_PB_CACHE_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "repro_pb"
    )
    return os.path.join(base, "graphs")


# Cache dirs whose save failure was already reported: the warning fires
# once per directory per process, so an unwritable REPRO_PB_CACHE_DIR in
# CI is visible without spamming one warning per graph.
_SAVE_WARNED: set = set()


def cached_graph(key: str, maker) -> COO:
    """Load a generated graph from the npz cache, or generate and save.

    ``key`` encodes generator + parameters + seed (the full determinism
    domain) and every entry embeds ``GRAPH_GEN_VERSION``, so a cache hit
    is bit-identical to regeneration by the CURRENT generators — an
    entry written by an older generator or npz layout misses and
    regenerates. A corrupt file regenerates silently; an unwritable
    cache dir skips persistence with a one-time warning naming the path
    (a silent skip once presented as a mystery per-run slowdown).
    """
    import os

    import zipfile

    path = os.path.join(_graph_cache_dir(), f"{key}.npz")
    try:
        with np.load(path) as z:
            if (
                "gen_version" in z.files
                and int(z["gen_version"]) == GRAPH_GEN_VERSION
            ):
                return _to_coo(z["src"], z["dst"], int(z["num_nodes"]))
    except (OSError, KeyError, ValueError, zipfile.BadZipFile):
        pass  # missing/corrupt/truncated cache entry: regenerate below
    g = maker()
    try:
        os.makedirs(_graph_cache_dir(), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:  # file handle: savez can't rename it
            np.savez(
                f,
                src=np.asarray(g.src),
                dst=np.asarray(g.dst),
                num_nodes=np.int64(g.num_nodes),
                gen_version=np.int64(GRAPH_GEN_VERSION),
            )
        os.replace(tmp, path)
    except OSError as e:
        d = _graph_cache_dir()
        if d not in _SAVE_WARNED:
            _SAVE_WARNED.add(d)
            warnings.warn(
                f"graph cache save failed under {d!r} ({e}); graphs will "
                "regenerate every run (set REPRO_PB_CACHE_DIR to a "
                "writable directory)",
                RuntimeWarning,
                stacklevel=2,
            )
    return g


def graph_suite(scale: str = "bench") -> dict:
    """The 5-graph suite mirroring the paper's inputs.

    scale='bench' sizes target a single-core CPU container (~1-4M edges);
    scale='smoke' is for tests (~10-50k edges). Bench graphs are cached
    under ``~/.cache/repro_pb/graphs`` (``REPRO_PB_CACHE_DIR`` overrides)
    because regenerating gen_kron(18, 8) from scratch on every benchmark
    invocation dominates harness start-up.
    """
    if scale == "bench":
        # the key's version suffix is DERIVED from GRAPH_GEN_VERSION:
        # key text and the version embedded in the npz can never drift
        # apart again (a hardcoded "_v1" once outlived a bump to v2)
        v = f"v{GRAPH_GEN_VERSION}"
        return {
            "DBP": cached_graph(f"powerlaw_n18_d8_s1_{v}", lambda: gen_powerlaw(1 << 18, 8, seed=1)),
            "KRON": cached_graph(f"kron_s18_d8_s2_{v}", lambda: gen_kron(18, 8, seed=2)),
            "URND": cached_graph(f"uniform_n18_d8_s3_{v}", lambda: gen_uniform(1 << 18, 8, seed=3)),
            "EURO": cached_graph(f"road_512_s4_{v}", lambda: gen_road(512, seed=4)),
            "HBUBL": cached_graph(f"bubbles_512_s5_{v}", lambda: gen_bubbles(512, seed=5)),
        }
    return dict(_smoke_suite())


@functools.lru_cache(maxsize=1)
def _smoke_suite() -> dict:
    """The 5 smoke graphs, generated once per process: the test suite
    calls ``graph_suite("smoke")`` hundreds of times per pytest run and
    the graphs are deterministic by seed, so regeneration was pure
    waste. ``graph_suite`` hands out a fresh dict each call (callers may
    pop/mutate the mapping); the COO entries are shared — they are
    treated as immutable everywhere."""
    return {
        "DBP": gen_powerlaw(1 << 10, 4, seed=1),
        "KRON": gen_kron(10, 4, seed=2),
        "URND": gen_uniform(1 << 10, 4, seed=3),
        "EURO": gen_road(32, seed=4),
        "HBUBL": gen_bubbles(32, seed=5),
    }
