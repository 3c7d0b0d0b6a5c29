"""Hypothesis property tests on the system's invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro import compat
from repro.core import COO, CobraPlan, get_default_executor
from repro.core import pb as pb_core
from repro.core.executor import execute_reduce
from repro.core.cobra import hierarchical_binning
from repro.core.neighbor_populate import build_csr_oracle, build_csr_pb
from repro.core.scatter import pb_scatter_add, scatter_add_baseline
from repro.kernels import ops, ref


SET = settings(max_examples=25, deadline=None)


indices_strategy = st.lists(st.integers(0, 199), min_size=1, max_size=300)


@SET
@given(idx=indices_strategy, bin_range=st.sampled_from([1, 7, 32, 200]))
def test_binning_is_stable_permutation(idx, bin_range):
    """Binning outputs a permutation of the input, sorted by bin id, and
    stable within each bin — the invariant that makes non-commutative PB
    correct (paper §2)."""
    idx = jnp.asarray(idx, jnp.int32)
    val = jnp.arange(idx.shape[0], dtype=jnp.int32)  # original positions
    nb = -(-200 // bin_range)
    bins = pb_core.binning_sort(idx, val, bin_range, nb)
    got_idx = np.asarray(bins.idx)
    got_val = np.asarray(bins.val)
    # permutation: same multiset
    assert sorted(got_idx.tolist()) == sorted(np.asarray(idx).tolist())
    # sorted by bin id
    bids = got_idx // bin_range
    assert (np.diff(bids) >= 0).all()
    # stability: original positions increase within each bin
    for b in np.unique(bids):
        sel = got_val[bids == b]
        assert (np.diff(sel) > 0).all()
    # starts consistent with histogram
    counts = np.bincount(np.asarray(idx) // bin_range, minlength=nb)
    assert np.array_equal(np.diff(np.asarray(bins.starts)), counts)


@SET
@given(
    idx=indices_strategy,
    fanouts=st.sampled_from([(4,), (2, 4), (4, 4, 4)]),
)
def test_hierarchical_equals_flat_binning(idx, fanouts):
    """COBRA's multi-pass composition == a single stable fine partition."""
    idx = jnp.asarray(idx, jnp.int32)
    val = jnp.arange(idx.shape[0], dtype=jnp.int32)
    n = 200
    total = 1
    for f in fanouts:
        total *= f
    final_range = max(1, -(-n // total))
    plan = CobraPlan(num_indices=n, final_bin_range=final_range, level_fanouts=tuple(fanouts))
    got = hierarchical_binning(idx, val, plan, method="sort")
    nb = -(-n // final_range)
    want_i, want_v = ref.binned_stream_ref(
        (idx // final_range).astype(jnp.int32), idx, val, nb
    )
    np.testing.assert_array_equal(np.asarray(got.idx), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got.val), np.asarray(want_v))


@SET
@given(
    edges=st.lists(
        st.tuples(st.integers(0, 31), st.integers(0, 31)), min_size=1, max_size=200
    ),
    bin_range=st.sampled_from([1, 4, 32]),
)
def test_el_to_csr_invariant_under_any_bin_range(edges, bin_range):
    """EL->CSR output is independent of the bin range AND exactly matches
    the sequential Algorithm 1 oracle (stability preserves EL order)."""
    src = jnp.asarray([e[0] for e in edges], jnp.int32)
    dst = jnp.asarray([e[1] for e in edges], jnp.int32)
    g = COO(src, dst, 32)
    oracle = build_csr_oracle(g)
    got = build_csr_pb(g, bin_range)
    np.testing.assert_array_equal(np.asarray(got.offsets), np.asarray(oracle.offsets))
    np.testing.assert_array_equal(np.asarray(got.neighs), np.asarray(oracle.neighs))


@SET
@given(
    idx=st.lists(st.integers(0, 63), min_size=1, max_size=200),
    seed=st.integers(0, 1000),
)
def test_pb_scatter_add_equals_baseline(idx, seed):
    idx = jnp.asarray(idx, jnp.int32)
    upd = jnp.asarray(
        np.random.default_rng(seed).normal(size=(idx.shape[0], 4)), jnp.float32
    )
    a = scatter_add_baseline(idx, upd, 64)
    b = pb_scatter_add(idx, upd, 64, coalesce=True)
    c = pb_scatter_add(idx, upd, 64, coalesce=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5)


@SET
@given(
    idx=st.lists(st.integers(0, 63), min_size=1, max_size=200),
    op=st.sampled_from(["add", "min", "max"]),
    method=st.sampled_from(["sort", "counting", "fused"]),
    seed=st.integers(0, 100),
)
def test_reduce_stream_parity_across_ops_and_methods(idx, op, method, seed):
    """Executor reduce == the dense scatter oracle for every (op, method)
    pair serving exercises — int32 values, so equality is exact and any
    ordering bug in the min/max identity handling surfaces bit-for-bit."""
    ex = get_default_executor()
    idx = jnp.asarray(idx, jnp.int32)
    val = jnp.asarray(
        np.random.default_rng(seed).integers(-50, 50, idx.shape[0]), jnp.int32
    )
    got = ex.reduce_stream(idx, val, out_size=64, op=op, method=method)
    want = ref.scatter_reduce_ref(idx, val, 64, op=op)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@SET
@given(
    b=st.integers(1, 4),
    m=st.integers(1, 48),
    op=st.sampled_from(["add", "min", "max"]),
    method=st.sampled_from(["sort", "counting", "fused"]),
    seed=st.integers(0, 100),
)
def test_reduce_streams_batched_equals_per_lane_loop(b, m, op, method, seed):
    """The (B, m) batched reduce (one decision, one vmapped program — the
    serving coalescing primitive) computes per lane exactly what B
    independent single-stream reduces compute."""
    ex = get_default_executor()
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, 32, (b, m)), jnp.int32)
    val = jnp.asarray(rng.integers(-9, 9, (b, m)), jnp.int32)
    got = ex.reduce_streams(idx, val, out_size=32, op=op, method=method)
    want = jnp.stack(
        [
            ex.reduce_stream(idx[q], val[q], out_size=32, op=op, method=method)
            for q in range(b)
        ]
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@SET
@given(
    idx=st.lists(st.integers(0, 63), min_size=1, max_size=160),
    op=st.sampled_from(["add", "max"]),
    feature_dim=st.sampled_from([1, 3, 8]),
    dtype=st.sampled_from(["float32", "int32"]),
    seed=st.integers(0, 100),
)
def test_row_reduce_parity_fused_two_phase_segment_sum(
    idx, op, feature_dim, dtype, seed
):
    """Row-valued (m, F) reduce parity (DESIGN.md §14): for int32 values
    and for max, the fused row-block path, both two-phase pipelines, and
    XLA ``segment_sum`` (op=add) agree BIT-EXACTLY with the dense oracle;
    op=max is exact by idempotence. A float32 add differs by summation
    order: fused and segment_sum sum in stream order and equal the oracle
    bit for bit; the two-phase pipelines sum each output along a pairwise
    tree (``pb.pairwise_segment_sum``), equal each other bit for bit, and
    lie within the tree's rounding bound of a float64 sum."""
    ex = get_default_executor()
    idx = jnp.asarray(idx, jnp.int32)
    m = int(idx.shape[0])
    rng = np.random.default_rng(seed)
    if dtype == "int32":
        val = jnp.asarray(rng.integers(-50, 50, (m, feature_dim)), jnp.int32)
    else:
        val = jnp.asarray(rng.standard_normal((m, feature_dim)), jnp.float32)
    arms = {
        "fused": execute_reduce(idx, val, out_size=64, op=op, method="fused"),
        "sort": ex.reduce_stream(idx, val, out_size=64, op=op, method="sort"),
        "counting": ex.reduce_stream(
            idx, val, out_size=64, op=op, method="counting"
        ),
    }
    if op == "add":
        arms["segment_sum"] = compat.segment_sum(val, idx, num_segments=64)
    want = ref.scatter_reduce_ref(idx, val, 64, op=op)
    tree_order = op == "add" and dtype == "float32"
    for arm, got in arms.items():
        assert got.dtype == val.dtype, arm
        if tree_order and arm in ("sort", "counting"):
            # within the roundings of a tree of depth ceil(log2 m) + 1
            x = np.asarray(val, np.float64)
            exact, mags = np.zeros((64, feature_dim)), np.zeros((64, feature_dim))
            np.add.at(exact, np.asarray(idx), x)
            np.add.at(mags, np.asarray(idx), np.abs(x))
            depth = int(np.ceil(np.log2(max(2, m)))) + 1
            err = np.abs(np.asarray(got, np.float64) - exact)
            assert np.all(err <= depth * 2.0**-24 * mags), arm
            continue
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want), err_msg=arm
        )
    if tree_order:
        np.testing.assert_array_equal(np.asarray(arms["sort"]), np.asarray(arms["counting"]))


@SET
@given(
    keys=st.lists(st.integers(0, 15), min_size=1, max_size=200),
    block=st.sampled_from([32, 64]),
)
def test_histogram_kernel_property(keys, block):
    keys = jnp.asarray(keys, jnp.int32)
    got = ops.histogram(keys, 16, block=block)
    np.testing.assert_array_equal(
        np.asarray(got), np.bincount(np.asarray(keys), minlength=16)
    )


@SET
@given(
    keys=st.lists(st.integers(0, 7), min_size=1, max_size=150),
    cap=st.sampled_from([64, 128]),
)
def test_cobra_kernel_property(keys, cap):
    """C-Buffer kernel == stable sort for arbitrary key streams (evictions
    at any fill pattern must preserve order)."""
    idx = jnp.asarray(keys, jnp.int32) * 8  # bin = idx//8 = original key
    val = jnp.arange(idx.shape[0], dtype=jnp.int32)
    bins = ops.cobra_binning_pass(
        idx, val, bin_range=8, num_bins=8, block=64, cap=cap
    )
    want_i, want_v = ref.binned_stream_ref(
        (idx // 8).astype(jnp.int32), idx, val, 8
    )
    np.testing.assert_array_equal(np.asarray(bins.idx), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(bins.val), np.asarray(want_v))


@SET
@given(
    edges=st.lists(
        st.tuples(st.integers(0, 31), st.integers(0, 31)), min_size=1, max_size=120
    ),
    updates=st.lists(
        st.tuples(st.integers(0, 31), st.integers(0, 31), st.booleans()),
        min_size=0,
        max_size=60,
    ),
    method=st.sampled_from(["sort", "counting", "fused"]),
)
def test_apply_edge_batch_equals_multiset_merge(edges, updates, method):
    """Delta-merging ANY batch into a SlackCSR == building from scratch
    on ``coo (+) batch`` as a multiset (DESIGN.md §15), under every
    forced reduce method. Zero headroom + min_slack=1 keeps the regrow
    path hot; deletes may miss (no-op) or hit duplicates (remove one
    occurrence each). Deterministic twins live in
    tests/test_updates.py::test_delta_merge_matches_from_scratch_build."""
    from repro.core import (
        apply_edge_batch,
        build_slack_csr,
        csr_equal_as_sets,
        make_batch,
        merge_batch_coo,
    )

    g = COO(
        src=jnp.asarray([e[0] for e in edges], jnp.int32),
        dst=jnp.asarray([e[1] for e in edges], jnp.int32),
        num_nodes=32,
    )
    batch = make_batch(
        [u[0] for u in updates], [u[1] for u in updates], [u[2] for u in updates]
    )
    slack = build_slack_csr(g, headroom=0.0, min_slack=1)
    res = apply_edge_batch(slack, batch, method=method, rebuild_slack_frac=0.0)
    want = build_csr_oracle(merge_batch_coo(g, batch))
    assert csr_equal_as_sets(res.graph.to_csr(), want)
    assert res.inserted == batch.num_inserts
    assert res.deleted + res.missed_deletes == batch.num_deletes


@SET
@given(
    n_tok=st.integers(1, 40),
    top_k=st.sampled_from([1, 2]),
    seed=st.integers(0, 100),
)
def test_moe_dispatch_conservation(n_tok, top_k, seed):
    """With ample capacity, PB dispatch output == dense oracle for any
    token count / top_k (no token lost or double-counted)."""
    import dataclasses

    import repro.models.layers as L
    from repro.models.config import ModelConfig
    from repro.models.params import unbox

    cfg = ModelConfig(
        name="p", family="moe", num_layers=1, d_model=16, num_heads=2,
        num_kv_heads=2, d_ff=32, vocab_size=64, num_experts=4, top_k=top_k,
        capacity_factor=float(4 * top_k), param_dtype="float32",
        compute_dtype="float32",
    )
    p, _ = unbox(L.init_moe(jax.random.PRNGKey(seed), cfg))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, n_tok, 16))
    y_pb = L.moe_apply(p, x, cfg)
    y_dense = L.moe_apply(p, x, dataclasses.replace(cfg, moe_dispatch="dense"))
    np.testing.assert_allclose(np.asarray(y_pb), np.asarray(y_dense), atol=2e-4)
