"""Fused single-sweep PB (DESIGN.md §8): kernel + executor equivalence
against kernels/ref.py, consumer end-to-end agreement, the commutativity
guard, and the graph/npz cache."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    PBExecutor,
    REDUCE_METHODS,
    connected_components,
    connected_components_fused,
    graph_suite,
    pagerank_coo_scatter,
    pagerank_fused,
)
from repro.core import pb as pb_core
from repro.kernels import ref
from repro.kernels.fused import (
    cobra_bin_accumulate_pallas,
    cobra_bin_accumulate_rows_pallas,
    reduce_identity,
)


def _random_stream(n, m, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        val = jnp.asarray(rng.integers(-50, 50, m), dtype)
    else:
        val = jnp.asarray(rng.normal(size=m), dtype)
    return idx, val


def _assert_reduce(got, idx, val, n, op="add"):
    want = ref.scatter_reduce_ref(idx, val, n, op=op)
    if jnp.issubdtype(val.dtype, jnp.integer):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# -- the Pallas kernel (interpret mode) ------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("op", ["add", "min"])
def test_fused_kernel_matches_scatter_ref(dtype, op):
    """cobra_bin_accumulate == dense scatter-reduce, with the binned
    stream never materialized (float32/int32, add/min)."""
    n = 777  # non-pow2: ragged final bin
    idx, val = _random_stream(n, 3001, seed=1, dtype=dtype)
    got = cobra_bin_accumulate_pallas(
        idx, val, num_indices=n, bin_range=100, op=op,
        block=256, cap=512, interpret=True,
    )
    _assert_reduce(got, idx, val, n, op=op)


def test_fused_kernel_single_bin_and_empty():
    n = 50
    idx, val = _random_stream(n, 400, seed=3)
    got = cobra_bin_accumulate_pallas(
        idx, val, num_indices=n, bin_range=n, block=128, cap=512,
    )
    _assert_reduce(got, idx, val, n)
    empty = cobra_bin_accumulate_pallas(
        jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32),
        num_indices=10, bin_range=5,
    )
    assert empty.shape == (10,) and float(jnp.abs(empty).sum()) == 0.0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("op", ["add", "max"])
@pytest.mark.parametrize("f_tile", [None, 3])
def test_fused_rows_kernel_matches_scatter_ref(dtype, op, f_tile):
    """The row-block (SpMM) kernel == dense row scatter-reduce, with the
    feature axis tiled (f_tile=3 over F=7 exercises the ragged final
    tile and its padding columns)."""
    n, F = 301, 7
    rng = np.random.default_rng(31)
    idx = jnp.asarray(rng.integers(0, n, 1500), jnp.int32)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        val = jnp.asarray(rng.integers(-50, 50, (1500, F)), dtype)
    else:
        val = jnp.asarray(rng.normal(size=(1500, F)), dtype)
    got = cobra_bin_accumulate_rows_pallas(
        idx, val, num_indices=n, bin_range=50, op=op,
        block=256, cap=512, f_tile=f_tile, interpret=True,
    )
    _assert_reduce(got, idx, val, n, op=op)


def test_fused_rows_kernel_edges():
    """Empty stream, single bin, F == f_tile == 1 (degenerate scalar),
    and the (m, 0) feature-less block all hold shape/identity."""
    n = 40
    rng = np.random.default_rng(33)
    idx = jnp.asarray(rng.integers(0, n, 300), jnp.int32)
    val = jnp.asarray(rng.normal(size=(300, 1)), jnp.float32)
    got = cobra_bin_accumulate_rows_pallas(
        idx, val, num_indices=n, bin_range=n, block=128,
        cap=512, interpret=True,
    )
    _assert_reduce(got, idx, val, n)
    empty = cobra_bin_accumulate_rows_pallas(
        jnp.zeros((0,), jnp.int32), jnp.zeros((0, 4), jnp.float32),
        num_indices=10, bin_range=5,
    )
    assert empty.shape == (10, 4) and float(jnp.abs(empty).sum()) == 0.0
    fless = cobra_bin_accumulate_rows_pallas(
        idx, jnp.zeros((300, 0), jnp.float32), num_indices=n, bin_range=5,
    )
    assert fless.shape == (n, 0)


def test_fused_kernel_rejects_non_commutative_op():
    with pytest.raises(ValueError, match="commutative"):
        cobra_bin_accumulate_pallas(
            jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.float32),
            num_indices=4, bin_range=2, op="concat",
        )


def test_reduce_identity_values():
    assert float(reduce_identity("add", jnp.float32)) == 0.0
    assert int(reduce_identity("min", jnp.int32)) == np.iinfo(np.int32).max


# -- the executor reduce_stream path ---------------------------------------


@pytest.mark.parametrize("method", REDUCE_METHODS)
def test_reduce_stream_all_methods_match_ref(method):
    """Every reduce method — the four two-phase pipelines and the fused
    single sweep — produces the identical dense reduction."""
    ex = PBExecutor()
    for seed, (n, m, r) in enumerate(
        [(200, 300, 7), (1000, 5000, 64), (513, 2000, 32)]
    ):
        idx, val = _random_stream(n, m, seed)
        got = ex.reduce_stream(idx, val, out_size=n, bin_range=r, method=method)
        _assert_reduce(got, idx, val, n)


@pytest.mark.parametrize("method", REDUCE_METHODS)
def test_reduce_stream_empty(method):
    ex = PBExecutor()
    got = ex.reduce_stream(
        jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32),
        out_size=100, bin_range=10, method=method,
    )
    assert got.shape == (100,) and float(jnp.abs(got).sum()) == 0.0


@pytest.mark.parametrize("method", REDUCE_METHODS)
def test_reduce_stream_single_bin_and_non_pow2(method):
    ex = PBExecutor()
    idx, val = _random_stream(50, 400, seed=3)
    got = ex.reduce_stream(idx, val, out_size=50, bin_range=50, method=method)
    _assert_reduce(got, idx, val, 50)
    n = 777
    idx, val = _random_stream(n, 3001, seed=5)
    got = ex.reduce_stream(idx, val, out_size=n, bin_range=100, method=method)
    _assert_reduce(got, idx, val, n)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_reduce_stream_dtypes(dtype):
    ex = PBExecutor()
    idx, val = _random_stream(400, 2000, seed=7, dtype=dtype)
    for method in ("fused", "counting"):
        got = ex.reduce_stream(idx, val, out_size=400, bin_range=32, method=method)
        assert got.dtype == jnp.dtype(dtype)
        _assert_reduce(got, idx, val, 400)


def test_reduce_stream_min_and_auto():
    ex = PBExecutor()
    idx, val = _random_stream(300, 4000, seed=9, dtype=jnp.int32)
    got = ex.reduce_stream(idx, val, out_size=300, op="min")  # auto decide
    _assert_reduce(got, idx, val, 300, op="min")
    d = ex.decide(300, 4000, kind="reduce")
    assert d.method in REDUCE_METHODS


def test_reduce_stream_rejects_non_commutative():
    """Order-sensitive consumers (neighbor placement, capacity clipping)
    must not slip onto the fused path: reduce_stream rejects anything
    outside the commutative op set."""
    ex = PBExecutor()
    idx, val = _random_stream(10, 20)
    for op in ("append", "set", "first", "concat"):
        with pytest.raises(ValueError, match="commutative"):
            ex.reduce_stream(idx, val, out_size=10, op=op)


def test_reduce_stream_smoke_suite_equivalence():
    """Fused == two-phase == dense scatter across the 5-graph smoke
    suite (degree-weighted contributions, the PageRank-shaped stream)."""
    ex = PBExecutor()
    for name, g in graph_suite("smoke").items():
        vals = jnp.ones((g.num_edges,), jnp.float32)
        want = ref.scatter_reduce_ref(g.dst, vals, g.num_nodes)
        for method in ("fused", "counting", "sort"):
            got = ex.reduce_stream(
                g.dst, vals, out_size=g.num_nodes, bin_range=64, method=method
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-3, err_msg=f"{name}/{method}"
            )


@pytest.mark.parametrize(
    "use_pallas, shape, batched, want",
    [
        (False, (4000,), False, "jnp"),  # interpret mode: the jnp sweep
        (True, (4000,), False, "pallas"),  # the (interpreted) C-Buffer kernel
        (True, (4000, 1), False, "pallas"),  # width-1 rows run the scalar kernel
        (True, (4000,), True, "jnp"),  # lanes off TPU: use_pallas never interprets them
    ],
)
def test_fused_decisions_name_their_realization(tmp_path, use_pallas, shape, batched, want):
    """Every fused decision-log entry says which realization ran, and
    that realization's answer matches the oracle."""
    ex = PBExecutor(use_pallas=use_pallas, cache_dir=str(tmp_path))
    n = 300
    idx, val = _random_stream(n, shape[0], seed=29)
    val = val.reshape(shape)
    if batched:
        got = ex.reduce_streams(idx[None], val[None], out_size=n)[0]
    else:
        got = ex.reduce_stream(idx, val, out_size=n)
    entry = ex.decision_log[-1]
    assert entry["method"] == "fused" and entry["realization"] == want
    _assert_reduce(got, idx, val, n)


@pytest.mark.parametrize("op", ["add", "max"])
def test_rows_wider_than_the_budget_run_one_column_at_a_time(tmp_path, op):
    """F=3 rows on a 2 MiB fast level: a 128-lane row tile does not fit,
    one column does, so the decision is fused at f_tile 1 and the scalar
    kernel (interpreted here) sweeps each column; the answer is exact."""
    from repro.core.plan import HardwareModel, fused_fits

    hw = HardwareModel(
        name="2mib", fast_levels=(2 << 20,), cbuffer_bytes=4096,
        dram_bandwidth=819e9, fast_bandwidth=20e12,
    )
    n, m, feat = 300, 4000, 3
    assert fused_fits(hw, n, f_tile=1) and not fused_fits(hw, n, f_tile=feat)
    ex = PBExecutor(hw=hw, use_pallas=True, cache_dir=str(tmp_path))
    rng = np.random.default_rng(7)
    idx = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    val = jnp.asarray(rng.integers(-50, 50, (m, feat)), jnp.int32)
    got = ex.reduce_stream(idx, val, out_size=n, op=op)
    entry = ex.decision_log[-1]
    assert (entry["method"], entry["f_tile"], entry["realization"]) == ("fused", 1, "pallas")
    _assert_reduce(got, idx, val, n, op=op)


@pytest.mark.parametrize("op", ["add", "min", "max"])
def test_batched_kernel_lanes_match_vmapped_sweep(op, monkeypatch):
    """The batched program a TPU runs for a fused reduce that fits — the
    C-Buffer kernel once per lane — equals the vmapped jnp sweep."""
    from repro.core import executor as executor_mod
    from repro.core.executor import _jitted_reduce_batched
    from repro.core.plan import FUSED_BIN_RANGE, HardwareModel

    n, lanes, m = 300, 3, 700
    rng = np.random.default_rng(31)
    idx = jnp.asarray(rng.integers(0, n, (lanes, m)), jnp.int32)
    val = jnp.asarray(rng.integers(-50, 50, (lanes, m)), jnp.int32)
    common = (n, FUSED_BIN_RANGE, None, "fused", op, 2048, True, None)
    budget = HardwareModel.tpu_v5e().fast_levels[-1]
    swept = _jitted_reduce_batched(*common, budget)(idx, val)
    # realize every lane as the kernel, in interpret mode here (a distinct
    # budget keys a fresh program)
    monkeypatch.setattr(executor_mod, "fused_realization", lambda *a, **k: "pallas")
    kernel = _jitted_reduce_batched(*common, budget - 1)(idx, val)
    _jitted_reduce_batched.cache_clear()
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(swept))


def test_hardware_model_by_device_kind_and_interpret_resolution():
    """A TPU's model comes from its device_kind (unknown kinds raise),
    and interpret mode is resolved in one place: interpret off TPU, and a
    kernel without a Mosaic lowering refuses to compile."""
    from repro.core.plan import HardwareModel
    from repro.kernels import interpret_only, resolve_interpret

    assert HardwareModel.for_device_kind("TPU v5 lite") == HardwareModel.tpu_v5e()
    with pytest.raises(ValueError, match="device_kind"):
        HardwareModel.for_device_kind("TPU v99")
    assert resolve_interpret(None) is True  # CPU backend
    assert resolve_interpret(False) is False  # lowering for a described TPU
    with pytest.raises(NotImplementedError, match="Mosaic"):
        interpret_only("scatter_rows_pallas", False)


def test_reduce_decisions_cached_separately_from_binning():
    """Reduce entries participate in the persisted cache schema under
    their own keys — a binning decision is not evidence for a reduction."""
    ex = PBExecutor()
    assert ex._key(100, 200, jnp.int32, kind="reduce") != ex._key(
        100, 200, jnp.int32, kind="bin"
    )
    d = ex.decide(1 << 10, 1 << 13, kind="reduce")
    assert d.method == "fused"  # accumulator fits the fast level
    big = ex.decide(1 << 26, 1 << 13, kind="reduce")
    assert big.method != "fused"  # accumulator exceeds the fast level


# -- sorted_within hint (satellite: the indices_are_sorted fix) ------------


def test_bin_read_sorted_within_hint():
    """bin_range==1 means the binned stream is elementwise sorted — the
    only case where XLA's indices_are_sorted claim is true; results must
    agree either way."""
    idx, val = _random_stream(64, 500, seed=11)
    b1 = pb_core.binning_sort(idx, val, 1, 64)
    out1 = pb_core.bin_read_scatter_add(b1, 64)  # sorted_within=1 implied
    b8 = pb_core.binning_sort(idx, val, 8, 8)
    out8 = pb_core.bin_read_scatter_add(b8, 64)  # bin-blocked only
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out8), atol=1e-4)
    _assert_reduce(out1, idx, val, 64)


def test_bin_read_pytree_values():
    """Satellite fix: Bin-Read used to crash on pytree values
    (``bins.val.shape`` on a tuple) even though binning accepts pytrees.
    Both binning methods' pytree outputs now reduce leafwise, matching
    the per-leaf single-array result exactly."""
    idx, val_f = _random_stream(100, 800, seed=21)
    val_i = jnp.arange(800, dtype=jnp.int32)
    for binner in (pb_core.binning_sort, pb_core.binning_counting):
        bins = binner(idx, {"a": val_f, "b": (val_i,)}, 16, 7)
        out = pb_core.bin_read_reduce(bins, 100, op="add")
        assert set(out) == {"a", "b"}
        single = binner(idx, val_f, 16, 7)
        np.testing.assert_allclose(
            np.asarray(out["a"]),
            np.asarray(pb_core.bin_read_reduce(single, 100, op="add")),
            atol=1e-5,
        )
        want_b = ref.scatter_reduce_ref(idx, val_i, 100, op="add")
        np.testing.assert_array_equal(np.asarray(out["b"][0]), np.asarray(want_b))
    # the single-array path is unchanged (min + scatter_add alias)
    bins = pb_core.binning_sort(idx, val_i, 16, 7)
    got_min = pb_core.bin_read_reduce(bins, 100, op="min")
    np.testing.assert_array_equal(
        np.asarray(got_min), np.asarray(ref.scatter_reduce_ref(idx, val_i, 100, op="min"))
    )


@pytest.mark.parametrize("op", ["add", "max"])
@pytest.mark.parametrize("F", [1, 3, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_row_reduce_bitexact_across_renderings(op, F, dtype):
    """Row-valued (m, F) parity, the deterministic twin of the
    tests/test_property.py hypothesis property (which skips where
    hypothesis is absent): for int32 values and for max, fused row-block
    == sort == counting == segment_sum (op=add) == dense oracle
    BIT-EXACTLY; max is exact by idempotence. A float32 add differs by
    summation order: the renderings that sum in stream order (fused's
    jnp sweep, segment_sum) equal the oracle bit for bit, the two-phase
    ones (sort, counting: the same stable binned stream, then the same
    pairwise tree, ``pb.pairwise_segment_sum``) equal each other bit for
    bit, and each lies within the tree's rounding bound of a float64
    sum."""
    from repro import compat

    ex = PBExecutor()
    n = 64
    rng = np.random.default_rng(43)
    for m in (1, 37, 300):
        idx = jnp.asarray(rng.integers(0, n, m), jnp.int32)
        if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
            val = jnp.asarray(rng.integers(-50, 50, (m, F)), dtype)
        else:
            val = jnp.asarray(rng.standard_normal((m, F)), dtype)
        arms = {
            "fused": ex.reduce_stream(
                idx, val, out_size=n, op=op, method="fused"
            ),
            "sort": ex.reduce_stream(idx, val, out_size=n, op=op, method="sort"),
            "counting": ex.reduce_stream(
                idx, val, out_size=n, op=op, method="counting"
            ),
        }
        if op == "add":
            arms["segment_sum"] = compat.segment_sum(val, idx, num_segments=n)
        want = ref.scatter_reduce_ref(idx, val, n, op=op)
        tree_order = op == "add" and dtype == jnp.float32
        for arm, got in arms.items():
            assert got.dtype == val.dtype, arm
            if tree_order and arm in ("sort", "counting"):
                assert_within_tree_rounding(got, idx, val, n)
                continue
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want), err_msg=f"{arm} m={m}"
            )
        if tree_order:
            np.testing.assert_array_equal(np.asarray(arms["sort"]), np.asarray(arms["counting"]))


def assert_within_tree_rounding(got, idx, val, n):
    """``got`` is the add of ``val`` at ``idx`` within the bound of a tree
    sum of depth ceil(log2 m) + 1 in float32: that many roundings of the
    sum of magnitudes, per output element."""
    idx, val = np.asarray(idx), np.asarray(val, np.float64)
    exact = np.zeros((n,) + val.shape[1:])
    mags = np.zeros_like(exact)
    np.add.at(exact, idx, val)
    np.add.at(mags, idx, np.abs(val))
    depth = int(np.ceil(np.log2(max(2, idx.shape[0])))) + 1
    bound = depth * 2.0**-24 * mags
    assert np.all(np.abs(np.asarray(got, np.float64) - exact) <= bound)


def test_max_reduce_identity_and_methods():
    """op="max" end to end: identity at untouched indices, every reduce
    method equal to the dense oracle."""
    assert int(reduce_identity("max", jnp.int32)) == np.iinfo(np.int32).min
    idx, val = _random_stream(300, 4000, seed=25, dtype=jnp.int32)
    ex = PBExecutor()
    want = ref.scatter_reduce_ref(idx, val, 300, op="max")
    for method in REDUCE_METHODS:
        got = ex.reduce_stream(idx, val, out_size=300, op="max", method=method)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want), err_msg=method
        )


# -- consumers -------------------------------------------------------------


def test_pagerank_fused_matches_scatter():
    g = graph_suite("smoke")["KRON"]
    a = pagerank_coo_scatter(g, iters=5).ranks
    for method in (None, "fused", "counting"):
        b = pagerank_fused(g, iters=5, method=method).ranks
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-8)


def test_components_fused_matches_baseline():
    g = graph_suite("smoke")["EURO"]
    a = connected_components(g, max_iters=128)
    b = connected_components_fused(g, max_iters=128)
    np.testing.assert_array_equal(np.asarray(a.labels), np.asarray(b.labels))


# -- graph cache (satellite) -----------------------------------------------


def test_cached_graph_roundtrip(tmp_path, monkeypatch):
    from repro.core.graph import cached_graph, gen_uniform

    monkeypatch.setenv("REPRO_PB_CACHE_DIR", str(tmp_path))
    calls = []

    def make():
        calls.append(1)
        return gen_uniform(256, 4, seed=13)

    g1 = cached_graph("uniform_t13_v1", make)
    g2 = cached_graph("uniform_t13_v1", make)
    assert len(calls) == 1  # second call served from npz
    np.testing.assert_array_equal(np.asarray(g1.src), np.asarray(g2.src))
    np.testing.assert_array_equal(np.asarray(g1.dst), np.asarray(g2.dst))
    assert g1.num_nodes == g2.num_nodes


def test_cached_graph_unwritable_dir_degrades(tmp_path, monkeypatch):
    blocker = tmp_path / "occupied"
    blocker.write_text("not a dir")
    monkeypatch.setenv("REPRO_PB_CACHE_DIR", str(blocker))
    from repro.core.graph import cached_graph, gen_uniform

    g = cached_graph("uniform_t17_v1", lambda: gen_uniform(128, 2, seed=17))
    assert g.num_edges == 256  # generation still works, cache silently off
