"""The two-phase reduce past the fused kernel's VMEM budget: the counting
binning loses no tuple, and its float add Bin-Read
(``pb.pairwise_segment_sum``) stays within the benchmark's 2e-4 of a
float64 reference where a scatter-add's one-after-another float32 sum at
a hub of 2^18 arcs does not."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import pb
from repro.core.executor import execute_reduce
from repro.core.graph import COO
from repro.core.pagerank import pagerank_fused

LIMIT = 2e-4  # rank_max_rel_err of the benchmark's PageRank cells


def ranks64(src, dst, n, iters):
    """float64 push PageRank: out-degree clamped to 1, damping 0.85."""
    inv = 1.0 / np.maximum(np.bincount(src, minlength=n), 1)
    r = np.full(n, 1.0 / n)
    for _ in range(iters):
        r = 0.15 / n + 0.85 * np.bincount(dst, weights=(r * inv)[src], minlength=n)
    return r


def hub_graph(hub_in, n=1 << 12, seed=7):
    """``hub_in`` sources of out-degree 3, each with one arc into vertex 0
    and two to random vertices, beside 4096 random arcs: every term the
    hub sums is the same float32 number, the worst case for a sum that
    rounds after each term."""
    rng = np.random.default_rng(seed)
    s = rng.integers(1, n, size=hub_in)
    src = np.concatenate([s, s, s, rng.integers(0, n, size=4096)])
    dst = np.concatenate([np.zeros(hub_in, np.int64), rng.integers(0, n, size=2 * hub_in + 4096)])
    return src, dst, n


def kron_graph(scale, seed):
    from repro.core.graph import gen_kron

    coo = gen_kron(scale, 16, seed=seed)
    return np.asarray(coo.src), np.asarray(coo.dst), coo.num_nodes


def uniform_graph(n, m, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, n, size=m), rng.integers(0, n, size=m), n


GRAPHS = {
    "hub_2^16": lambda: hub_graph(1 << 16),
    "hub_2^18": lambda: hub_graph(1 << 18),
    "kron12": lambda: kron_graph(12, seed=3),
    "uniform": lambda: uniform_graph(3000, 40_000, seed=5),
}


def rel_err(got, want):
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want) / want))


@pytest.mark.parametrize("iters", [1, 3])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_counting_pagerank_within_the_limit_of_float64(graph, iters):
    src, dst, n = GRAPHS[graph]()
    coo = COO(src=jnp.asarray(src, jnp.int32), dst=jnp.asarray(dst, jnp.int32), num_nodes=n)
    got = pagerank_fused(coo, iters=iters, method="counting").ranks
    assert rel_err(got, ranks64(src, dst, n, iters)) <= LIMIT


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_counting_execute_reduce_within_the_limit_of_float64(graph):
    src, dst, n = GRAPHS[graph]()
    w = 1.0 / np.maximum(np.bincount(src, minlength=n), 1)[src]
    got = execute_reduce(
        jnp.asarray(dst, jnp.int32), jnp.asarray(w, jnp.float32), out_size=n, op="add",
        method="counting",
    )
    want = np.bincount(dst, weights=w.astype(np.float32).astype(np.float64), minlength=n)
    hit = want > 0
    assert rel_err(np.asarray(got)[hit], want[hit]) <= LIMIT
    assert np.all(np.asarray(got)[~hit] == 0)


def test_hub_sum_error_does_not_grow_with_its_arcs():
    """At an index of 2^18 equal float32 terms the tree sum is within a
    few float32 roundings of the exact one; a sum in stream order is not."""
    m = 1 << 18
    v = np.full(m, np.float32(1.0 / 3.0))
    exact = m * np.float64(np.float32(1.0 / 3.0))
    got = pb.pairwise_segment_sum(jnp.zeros((m,), jnp.int32), jnp.asarray(v), 1)
    assert abs(float(got[0]) - exact) / exact <= 1e-6
    serial = np.cumsum(v, dtype=np.float32)[-1]
    assert abs(float(serial) - exact) / exact > 1e-5


@pytest.mark.parametrize(
    "n, bin_range, m",
    [
        (1000, 77, 5000),  # 13 bins; the last of three 2048-tuple blocks padded
        (1 << 24, 92681, (1 << 16) + 5),  # kron24's decision on a v5e: 182 bins
    ],
)
def test_ones_through_the_counting_reduce_equal_bincount(n, bin_range, m):
    """No tuple is lost or doubled in binning: the padding bin, the cursor
    clamp and the inverse permutation's destinations all place each tuple
    once."""
    rng = np.random.default_rng(11)
    idx = rng.integers(0, n, size=m)
    num_bins = -(-n // bin_range)
    bins = pb.binning_counting(
        jnp.asarray(idx, jnp.int32), jnp.arange(m, dtype=jnp.int32), bin_range, num_bins, block=2048,
    )
    assert int(bins.starts[-1]) == m
    assert np.array_equal(np.sort(np.asarray(bins.val)), np.arange(m))
    np.testing.assert_array_equal(np.asarray(bins.idx), idx[np.asarray(bins.val)])
    got = execute_reduce(
        jnp.asarray(idx, jnp.int32), jnp.ones((m,), jnp.float32), out_size=n, op="add",
        method="counting", bin_range=bin_range, num_bins=num_bins, block=2048,
    )
    np.testing.assert_array_equal(np.asarray(got), np.bincount(idx, minlength=n).astype(np.float32))


@pytest.mark.parametrize("presorted", [False, True])
@pytest.mark.parametrize("m, n, feat", [(1, 5, 0), (127, 10, 0), (129, 10, 3), (5000, 37, 0), (300, 1000, 2)])
def test_pairwise_segment_sum_matches_float64(m, n, feat, presorted):
    """Scalar lanes and row blocks, streams shorter and longer than a scan
    row; indices below 0 wrap as a scatter's do, and those outside the
    output after that are dropped."""
    rng = np.random.default_rng(m + n)
    idx = rng.integers(-n - 3, n + 3, size=m)
    if presorted:
        idx = np.sort(rng.integers(0, n, size=m))
    v = rng.standard_normal((m, feat) if feat else m).astype(np.float32)
    want = np.zeros((n,) + v.shape[1:])
    wrapped = np.where(idx < 0, idx + n, idx)
    keep = (wrapped >= 0) & (wrapped < n)
    np.add.at(want, wrapped[keep], v[keep].astype(np.float64))
    got = pb.pairwise_segment_sum(jnp.asarray(idx, jnp.int32), jnp.asarray(v), n, presorted=presorted)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_pairwise_segment_sum_of_nothing_is_zeros():
    out = pb.pairwise_segment_sum(jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32), 4)
    np.testing.assert_array_equal(np.asarray(out), np.zeros(4, np.float32))
