"""Streaming graph mutation tests (DESIGN.md §15).

Contracts:

  1. SlackCSR round-trip — ``from_csr(c).to_csr()`` reproduces ``c``
     bit-for-bit at every headroom, and the layout invariants (counts,
     live degrees, slack fraction) hold on every smoke graph.
  2. Delta-merge exactness — ``apply_edge_batch`` is edge-set-equal to
     the from-scratch ``build_csr_oracle(merge_batch_coo(coo, batch))``
     across every batch shape the layout can hit (insert-only,
     delete-only, mixed, overflow-regrow, rebuild-threshold) under every
     forced reduce method. These parametrized cases are the
     deterministic twins of the hypothesis property in
     ``test_property.py::test_apply_edge_batch_equals_multiset_merge``
     (hypothesis is optional; these always run).
  3. Executor routing — the merge's reduces go through
     ``PBExecutor.reduce_stream(kind="update")`` and the decisions land
     in ``UpdateResult.decisions``.
  4. Incremental kernels — warm-started bfs / pagerank / connected
     components after an insert-only batch match their from-scratch
     runs on every smoke graph; batches with deletes take the exact
     full-recompute fallback.
  5. Serving epochs — a mutation through the frontend bumps the graph
     epoch, invalidates the memo by key construction, and the next
     global query is computed fresh on the mutated graph (ISSUE 9
     satellite regression).

Plus the two graph.py satellites: ``graph_suite("smoke")`` memoization
and the one-time cache-save warning naming the unwritable path.
"""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    COO,
    CSR,
    PBExecutor,
    SlackCSR,
    TOMBSTONE,
    apply_edge_batch,
    bfs,
    bfs_incremental,
    build_csr,
    build_csr_oracle,
    build_slack_csr,
    connected_components_fused,
    connected_components_incremental,
    csr_equal_as_sets,
    graph_suite,
    make_batch,
    merge_batch_coo,
    pagerank_incremental,
    random_edge_batch,
    touched_vertices,
)
from repro.core import graph as graph_mod
from repro.serving.graph_frontend import FakeClock, GraphFrontend, GraphQuery

SUITE = graph_suite("smoke")


@pytest.fixture(scope="module")
def ex(tmp_path_factory):
    # isolated autotune cache: decisions in these tests never depend on
    # whatever a previous benchmark run measured on this machine
    return PBExecutor(cache_dir=str(tmp_path_factory.mktemp("pbcache")))


# ---------------------------------------------------------------------------
# 1. SlackCSR round-trip + layout invariants.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SUITE))
@pytest.mark.parametrize("headroom", [0.0, 0.25, 1.0])
def test_slackcsr_roundtrip_is_exact(name, headroom):
    csr = build_csr(SUITE[name])
    s = SlackCSR.from_csr(csr, headroom=headroom, min_slack=2)
    back = s.to_csr()
    np.testing.assert_array_equal(
        np.asarray(back.offsets), np.asarray(csr.offsets)
    )
    np.testing.assert_array_equal(np.asarray(back.neighs), np.asarray(csr.neighs))
    assert s.num_edges == csr.num_edges
    np.testing.assert_array_equal(
        np.asarray(s.live_degrees()), np.diff(np.asarray(csr.offsets))
    )
    assert 0.0 < s.slack_fraction < 1.0


def _numpy_slack_layout(off, nei, headroom, min_slack):
    """The slab layout written out in host numpy, slot by slot."""
    off = np.asarray(off).astype(np.int64)
    nei = np.asarray(nei)
    deg = np.diff(off)
    cap = deg + np.maximum(min_slack, np.ceil(deg * headroom).astype(np.int64))
    soff = np.concatenate([[0], np.cumsum(cap)])
    slab = np.full(int(soff[-1]), TOMBSTONE, np.int32)
    seg = np.repeat(np.arange(deg.shape[0]), cap)
    r = np.arange(slab.shape[0]) - soff[seg]
    occ = r < deg[seg]
    slab[occ] = nei[(off[seg] + r)[occ]]
    return soff, slab, deg


def _hand_csr(degrees):
    """A CSR with the given out-degrees; neighbour ids spread over 0..n-1."""
    deg = np.asarray(degrees, np.int64)
    off = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    nei = (np.arange(int(off[-1])) * 7 % len(deg)).astype(np.int32)
    return CSR(offsets=jnp.asarray(off), neighs=jnp.asarray(nei), num_nodes=len(deg))


_HAND = {
    "zero_degree_first": [0, 0, 3, 1, 2],
    "zero_degree_last": [2, 5, 1, 0, 0, 0],
    "zero_degree_runs": [0, 4, 0, 0, 0, 2, 0, 1, 0, 0, 3, 0],
    "no_arcs": [0, 0, 0, 0],
    "single_vertex": [6],
    "single_isolated_vertex": [0],
    "deg50": [50],
}
_LAYOUT_CASES = [
    (name, headroom, min_slack)
    for name in sorted(SUITE)
    for headroom in (0.0, 0.25, 0.3, 1.0)
    for min_slack in (0, 4)
] + [
    (f"hand:{name}", headroom, min_slack)
    for name in _HAND
    for headroom, min_slack in ((0.0, 0), (0.3, 0), (0.25, 4))
]


@pytest.mark.parametrize("name,headroom,min_slack", _LAYOUT_CASES)
def test_slackcsr_device_layout_matches_numpy(name, headroom, min_slack):
    """``from_csr``'s device layout equals the host numpy layout entry by
    entry, with the capacities of float64 math (float32 would give
    ``ceil(50 * 0.3) = 16``)."""
    if name.startswith("hand:"):
        csr = _hand_csr(_HAND[name[5:]])
    else:
        csr = build_csr(SUITE[name])
    s = SlackCSR.from_csr(csr, headroom=headroom, min_slack=min_slack)
    want = _numpy_slack_layout(csr.offsets, csr.neighs, headroom, min_slack)
    for got, exp in zip((s.offsets, s.neighs, s.counts), want):
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), exp)
    assert s.num_nodes == csr.num_nodes
    if name == "hand:deg50" and (headroom, min_slack) == (0.3, 0):
        assert np.asarray(s.offsets).tolist() == [0, 65]


@pytest.mark.parametrize(
    "length,total",
    [
        (0, 0),
        (1, 2**31 - 1),
        (127, 1000),
        (128, 2**31 - 1),
        (129, 2**31 - 1),
        (128 * 128 + 1, 2**31 - 1),
        (128**3 + 5, 2**31 - 1),
    ],
)
def test_device_prefix_sum_is_exact(length, total):
    """The layout's int32 prefix sums (bytes through bfloat16 matmuls)
    equal numpy's across row and level boundaries, with every byte of
    the values in use and the running sum up to 2**31 - 1."""
    rng = np.random.default_rng(length)
    x = np.zeros(length, np.int64)
    if length:
        cuts = np.sort(rng.integers(0, total + 1, length - 1))
        x = np.diff(np.concatenate([[0], cuts, [total]]))
    got = np.asarray(graph_mod._prefix_sum(jnp.asarray(x.astype(np.int32))))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, np.cumsum(x))


def test_slackcsr_refuses_2_31_slots():
    csr = _hand_csr([1000])
    with pytest.raises(ValueError, match="2\\*\\*31"):
        SlackCSR.from_csr(csr, headroom=3e6, min_slack=0)
    # the limit is on the total: two empty slabs of 2**30 slots reach it
    with pytest.raises(ValueError, match="2\\*\\*31"):
        SlackCSR.from_csr(_hand_csr([0, 0]), headroom=0.0, min_slack=2**30)


def test_slackcsr_rejects_negative_headroom():
    csr = build_csr(SUITE["EURO"])
    with pytest.raises(ValueError):
        SlackCSR.from_csr(csr, headroom=-0.1)
    with pytest.raises(ValueError):
        SlackCSR.from_csr(csr, min_slack=-1)


# ---------------------------------------------------------------------------
# 2. Delta-merge exactness: every batch shape x every forced method.
#    (Deterministic twins of the hypothesis property.)
# ---------------------------------------------------------------------------


def _shaped_batch(shape, coo):
    """(batch, build kwargs, apply kwargs) for one named batch shape."""
    if shape == "insert_only":
        return random_edge_batch(coo, 200, 0, seed=11), {}, {}
    if shape == "delete_only":
        return random_edge_batch(coo, 0, 200, seed=12), {}, {}
    if shape == "mixed":
        return random_edge_batch(coo, 150, 50, seed=13), {}, {}
    if shape == "overflow_regrow":
        # every insert lands on one hub vertex: its slab must overflow
        rng = np.random.default_rng(14)
        hub = int(np.argmax(np.bincount(np.asarray(coo.src))))
        b = make_batch(
            np.full(64, hub), rng.integers(0, coo.num_nodes, 64), np.ones(64, bool)
        )
        return b, {}, {}
    assert shape == "rebuild_threshold"
    # zero headroom + a high threshold: the batch exhausts slack and the
    # merge must route through the PreprocessPipeline rebuild
    return (
        random_edge_batch(coo, 150, 50, seed=15),
        {"headroom": 0.0, "min_slack": 1},
        {"rebuild_slack_frac": 0.5},
    )


SHAPES = (
    "insert_only",
    "delete_only",
    "mixed",
    "overflow_regrow",
    "rebuild_threshold",
)


@pytest.mark.parametrize("method", ["sort", "counting", "fused"])
@pytest.mark.parametrize("shape", SHAPES)
def test_delta_merge_matches_from_scratch_build(shape, method, ex):
    coo = SUITE["DBP"]
    batch, build_kw, apply_kw = _shaped_batch(shape, coo)
    g0 = build_slack_csr(coo, **build_kw)
    res = apply_edge_batch(g0, batch, executor=ex, method=method, **apply_kw)
    want = build_csr_oracle(merge_batch_coo(coo, batch))
    assert csr_equal_as_sets(res.graph.to_csr(), want)
    # bookkeeping: every insert landed; every delete (sampled from the
    # live edge list without replacement) tombstoned exactly one slot
    assert res.inserted == batch.num_inserts
    assert res.deleted == batch.num_deletes
    assert res.missed_deletes == 0
    if shape == "overflow_regrow":
        assert res.regrown >= 1
    if shape == "rebuild_threshold":
        assert res.rebuilt and res.report is not None
    else:
        assert not res.rebuilt


@pytest.mark.parametrize("name", sorted(SUITE))
def test_delta_merge_auto_method_every_graph(name, ex):
    coo = SUITE[name]
    batch = random_edge_batch(coo, 96, 32, seed=21)
    res = apply_edge_batch(build_slack_csr(coo), batch, executor=ex)
    want = build_csr_oracle(merge_batch_coo(coo, batch))
    assert csr_equal_as_sets(res.graph.to_csr(), want)


def test_update_reduces_carry_kind_update(ex):
    coo = SUITE["KRON"]
    res = apply_edge_batch(
        build_slack_csr(coo), random_edge_batch(coo, 64, 16, seed=3), executor=ex
    )
    upd = [d for d in res.decisions if d.get("kind") == "update"]
    # one decision per reduce in the delta pair (degree delta + insert
    # counts) — the update namespace is what fig10 reads back
    assert len(upd) == 2
    assert all(d["method"] in ("sort", "counting", "fused") for d in upd)


def test_multiset_delete_semantics_and_missed_count(ex):
    coo = SUITE["EURO"]
    u = int(np.asarray(coo.src)[0])
    v = int(np.asarray(coo.dst)[0])
    occ = int(
        ((np.asarray(coo.src) == u) & (np.asarray(coo.dst) == v)).sum()
    )
    k = occ + 2  # two more deletes than live occurrences
    batch = make_batch(np.full(k, u), np.full(k, v), np.zeros(k, bool))
    res = apply_edge_batch(build_slack_csr(coo), batch, executor=ex)
    assert res.deleted == occ
    assert res.missed_deletes == 2
    assert csr_equal_as_sets(
        res.graph.to_csr(), build_csr_oracle(merge_batch_coo(coo, batch))
    )


def test_empty_batch_is_identity(ex):
    coo = SUITE["EURO"]
    g0 = build_slack_csr(coo)
    res = apply_edge_batch(g0, make_batch([], [], []), executor=ex)
    assert csr_equal_as_sets(res.graph.to_csr(), build_csr(coo))
    assert res.inserted == res.deleted == res.missed_deletes == 0


def test_batch_endpoints_are_validated(ex):
    coo = SUITE["EURO"]
    bad = make_batch([0], [coo.num_nodes], [True])
    with pytest.raises(ValueError, match="outside"):
        apply_edge_batch(build_slack_csr(coo), bad, executor=ex)


def test_tombstones_consume_slack_until_rebuild(ex):
    """Deletes never free capacity in place — slack_fraction is monotone
    non-increasing under mutation until the rebuild compacts (the
    property that makes the rebuild threshold meaningful)."""
    coo = SUITE["URND"]
    g0 = build_slack_csr(coo, headroom=0.0, min_slack=1)
    res = apply_edge_batch(
        g0,
        random_edge_batch(coo, 128, 128, seed=5),
        executor=ex,
        allow_rebuild=False,
    )
    assert res.graph.slack_fraction <= g0.slack_fraction
    assert int((np.asarray(res.graph.neighs) == TOMBSTONE).sum()) > 0
    rebuilt = apply_edge_batch(
        res.graph,
        make_batch([], [], []),
        executor=ex,
        rebuild_slack_frac=1.0,  # force the compaction arm
    )
    assert rebuilt.rebuilt
    assert rebuilt.graph.slack_fraction > res.graph.slack_fraction


# ---------------------------------------------------------------------------
# 4. Incremental kernels vs from-scratch.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SUITE))
def test_incremental_kernels_match_from_scratch(name, ex):
    coo = SUITE[name]
    b_ins = random_edge_batch(coo, 64, 0, seed=7)
    res = apply_edge_batch(build_slack_csr(coo), b_ins, executor=ex)
    csr_new = res.graph.to_csr()
    touched, has_deletes = touched_vertices(b_ins)
    assert not has_deletes

    prev = bfs(build_csr(coo), 0, executor=ex, with_parents=False)
    inc, mode = bfs_incremental(csr_new, 0, prev.dist, touched, executor=ex)
    assert mode == "incremental"
    full = bfs(csr_new, 0, executor=ex, with_parents=False)
    np.testing.assert_array_equal(np.asarray(inc.dist), np.asarray(full.dist))

    coo_new = merge_batch_coo(coo, b_ins)
    old = pagerank_incremental(coo, None, tol=1e-7)
    warm = pagerank_incremental(coo_new, old.ranks, tol=1e-7)
    cold = pagerank_incremental(coo_new, None, tol=1e-7)
    np.testing.assert_allclose(
        np.asarray(warm.ranks), np.asarray(cold.ranks), atol=1e-5
    )

    prev_cc = connected_components_fused(coo)
    cc_inc, cc_mode = connected_components_incremental(coo_new, prev_cc.labels)
    assert cc_mode == "incremental"
    cc_full = connected_components_fused(coo_new)
    np.testing.assert_array_equal(
        np.asarray(cc_inc.labels), np.asarray(cc_full.labels)
    )


def test_deletes_force_exact_full_fallback(ex):
    coo = SUITE["KRON"]
    batch = random_edge_batch(coo, 32, 32, seed=9)
    res = apply_edge_batch(build_slack_csr(coo), batch, executor=ex)
    csr_new = res.graph.to_csr()
    touched, has_deletes = touched_vertices(batch)
    assert has_deletes

    prev = bfs(build_csr(coo), 0, executor=ex, with_parents=False)
    inc, mode = bfs_incremental(
        csr_new, 0, prev.dist, touched, has_deletes=True, executor=ex
    )
    assert mode == "full"
    np.testing.assert_array_equal(
        np.asarray(inc.dist),
        np.asarray(bfs(csr_new, 0, executor=ex, with_parents=False).dist),
    )

    coo_new = merge_batch_coo(coo, batch)
    prev_cc = connected_components_fused(coo)
    cc_inc, cc_mode = connected_components_incremental(
        coo_new, prev_cc.labels, has_deletes=True
    )
    assert cc_mode == "full"
    np.testing.assert_array_equal(
        np.asarray(cc_inc.labels),
        np.asarray(connected_components_fused(coo_new).labels),
    )


def test_pagerank_incremental_validates_inputs():
    with pytest.raises(ValueError):
        pagerank_incremental(SUITE["EURO"], None, tol=0.0)
    with pytest.raises(ValueError):
        pagerank_incremental(SUITE["EURO"], None, max_iters=0)


# ---------------------------------------------------------------------------
# 5. Serving epochs: mutation invalidates the memo by key construction.
# ---------------------------------------------------------------------------


def test_mutation_bumps_epoch_and_serves_fresh_results(ex):
    coo = SUITE["DBP"]
    fe = GraphFrontend(executor=ex, max_batch=4, clock=FakeClock())
    fe.register_graph("g", coo, seed=0)

    q1 = GraphQuery(tenant="t", graph="g", kind="pagerank")
    fe.submit(q1)
    fe.run_until_drained()
    r0 = np.asarray(q1.result).copy()
    assert any(k[1] == 0 for k in fe._memo)  # memo key carries epoch 0

    # memo hit on the unchanged graph: same epoch -> same cached object
    q2 = GraphQuery(tenant="t", graph="g", kind="pagerank")
    fe.submit(q2)
    fe.run_until_drained()
    assert q2.result is q1.result

    ub = random_edge_batch(coo, 256, 64, seed=3)
    uq = GraphQuery(tenant="t", graph="g", kind="update", batch=ub)
    fe.submit(uq)
    fe.run_until_drained()
    assert fe._graphs["g"].epoch == 1
    assert int(uq.result[0]) == 1  # [epoch, inserted, deleted, missed]
    assert int(uq.result[1]) == ub.num_inserts

    # the regression this satellite guards: post-mutation query must be
    # computed fresh on the mutated graph, not served from the old memo
    q3 = GraphQuery(tenant="t", graph="g", kind="pagerank")
    fe.submit(q3)
    fe.run_until_drained()
    assert q3.result is not q1.result
    assert not np.allclose(r0, np.asarray(q3.result))
    assert all(k[1] == 1 for k in fe._memo if k[0] == "g")  # stale pruned


def test_update_queries_are_validated(ex):
    coo = SUITE["EURO"]
    fe = GraphFrontend(executor=ex, max_batch=2, clock=FakeClock())
    fe.register_graph("g", coo, seed=0)
    with pytest.raises(ValueError):
        fe.submit(GraphQuery(tenant="t", graph="g", kind="update"))  # no batch
    with pytest.raises(ValueError):
        fe.submit(
            GraphQuery(
                tenant="t",
                graph="g",
                kind="update",
                batch=make_batch([0], [coo.num_nodes], [True]),
            )
        )


# ---------------------------------------------------------------------------
# 6. graph.py satellites: suite memoization + warn-once cache save.
# ---------------------------------------------------------------------------


def test_smoke_suite_is_memoized_per_process():
    a = graph_suite("smoke")
    b = graph_suite("smoke")
    assert a is not b  # callers may mutate their dict
    for name in a:
        assert a[name] is b[name]  # the graphs themselves are shared


def test_cache_save_failure_warns_once_naming_the_path(tmp_path, monkeypatch):
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("")  # makedirs under a file -> OSError
    monkeypatch.setenv("REPRO_PB_CACHE_DIR", str(blocker))
    monkeypatch.setattr(graph_mod, "_SAVE_WARNED", set())
    mk = lambda: COO(
        src=np.zeros(1, np.int32), dst=np.zeros(1, np.int32), num_nodes=2
    )
    with pytest.warns(RuntimeWarning, match="not_a_dir"):
        graph_mod.cached_graph("warn_once_probe", mk)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a second warning would raise
        g = graph_mod.cached_graph("warn_once_probe", mk)
    assert g.num_nodes == 2
