"""Preprocessing pipeline subsystem (core/preprocess.py, DESIGN.md §10):
reorder-variant registry properties, dual CSR/CSC builds vs. oracles,
pipeline end-to-end equivalence across variants x build methods, the
fused-legality regression (no hardcoded method="fused" in core/), and
the vectorized csr_equal_as_sets.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    COO,
    CSR,
    PBExecutor,
    PreprocessPipeline,
    REORDER_VARIANTS,
    amortization_iters,
    build_csc,
    build_csr,
    build_csr_csc,
    build_csr_oracle,
    csr_equal_as_sets,
    get_default_executor,
    set_default_executor,
    spans,
    transpose_coo,
)
from repro.core.graph import degrees_from_coo, gen_powerlaw, gen_uniform
from repro.core.plan import HardwareModel
from repro.core.reorder import relabel_coo, reorder_mapping

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VARIANTS = tuple(REORDER_VARIANTS)


def _graph(seed=7, n=512, d=4):
    return gen_powerlaw(n, d, seed=seed)


# -- variant registry properties -------------------------------------------


@pytest.mark.parametrize("variant", VARIANTS)
def test_variant_mapping_is_permutation(variant):
    g = _graph()
    new_ids = np.asarray(reorder_mapping(variant, g.src, g.num_nodes, seed=3))
    assert np.array_equal(np.sort(new_ids), np.arange(g.num_nodes))


def test_identity_variant_is_noop():
    g = _graph()
    new_ids = np.asarray(reorder_mapping("identity", g.src, g.num_nodes))
    assert np.array_equal(new_ids, np.arange(g.num_nodes))


def test_hub_sort_hubs_first_tail_untouched():
    g = _graph(seed=9)
    deg = np.asarray(degrees_from_coo(g, by="src"))
    new_ids = np.asarray(reorder_mapping("hub_sort", g.src, g.num_nodes))
    order = np.argsort(new_ids)  # old ids in new order
    avg = deg.sum() // g.num_nodes
    is_hub = deg > avg
    nhubs = int(is_hub.sum())
    assert 0 < nhubs < g.num_nodes  # power-law input: both classes exist
    head, tail = order[:nhubs], order[nhubs:]
    # hubs occupy the head, in descending degree
    assert is_hub[head].all() and not is_hub[tail].any()
    assert np.all(deg[head][:-1] >= deg[head][1:])
    # the tail is untouched: original relative order preserved
    assert np.all(tail[:-1] < tail[1:])


def test_dbg_groups_by_degree_bucket_stably():
    g = _graph(seed=10)
    deg = np.asarray(degrees_from_coo(g, by="src"))
    new_ids = np.asarray(reorder_mapping("dbg", g.src, g.num_nodes))
    order = np.argsort(new_ids)
    bucket = np.floor(np.log2(deg.astype(np.float64) + 1.0)).astype(np.int64)
    b = bucket[order]
    # coarse buckets descending along new ids...
    assert np.all(b[:-1] >= b[1:])
    # ...and original id order within each bucket (stable grouping)
    same = b[:-1] == b[1:]
    assert np.all(order[:-1][same] < order[1:][same])


def test_random_variant_is_seeded():
    g = _graph()
    a = np.asarray(reorder_mapping("random", g.src, g.num_nodes, seed=1))
    b = np.asarray(reorder_mapping("random", g.src, g.num_nodes, seed=1))
    c = np.asarray(reorder_mapping("random", g.src, g.num_nodes, seed=2))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_unknown_variant_rejected():
    g = _graph()
    with pytest.raises(ValueError, match="unknown reorder variant"):
        reorder_mapping("sorted_by_vibes", g.src, g.num_nodes)
    with pytest.raises(ValueError, match="unknown reorder variant"):
        PreprocessPipeline(variant="sorted_by_vibes")


# -- dual CSR/CSC builds ----------------------------------------------------


@pytest.mark.parametrize("method", ["baseline", "pb", "cobra", "auto"])
def test_build_csc_equals_transpose_oracle(method):
    g = gen_uniform(300, 4, seed=21)
    csc = build_csc(g, method=method, bin_range=64)
    want = build_csr_oracle(transpose_coo(g))
    assert csr_equal_as_sets(csc, want)


def test_build_csr_csc_dual(method="auto"):
    g = _graph(seed=22)
    csr, csc = build_csr_csc(g, method=method)
    assert csr_equal_as_sets(csr, build_csr_oracle(g))
    assert csr_equal_as_sets(csc, build_csr_oracle(transpose_coo(g)))
    # the two layouts describe the same edge multiset, transposed
    assert csr.num_edges == csc.num_edges == g.num_edges


def test_build_csr_rejects_unknown_method():
    with pytest.raises(ValueError, match="unknown build method"):
        build_csr(_graph(), method="quantum")


# -- pipeline end-to-end ----------------------------------------------------


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("method", ["baseline", "pb", "cobra", "sharded"])
def test_pipeline_end_to_end(variant, method):
    """All variants x build methods: the rebuilt layouts equal the
    oracles of the relabeled graph, and the report accounts for every
    stage. ``sharded`` without a mesh exercises the single-device
    fallback (the 8-device equivalence runs in a subprocess below)."""
    g = gen_uniform(256, 4, seed=31)
    pipe = PreprocessPipeline(variant=variant, build_method=method, bin_range=64)
    res = pipe.run(g)
    rel = relabel_coo(g, res.new_ids)
    assert csr_equal_as_sets(res.csr, build_csr_oracle(rel))
    assert csr_equal_as_sets(res.csc, build_csr_oracle(transpose_coo(rel)))
    # degrees stage = histogram of the ORIGINAL ids
    np.testing.assert_array_equal(
        np.asarray(res.degrees), np.asarray(degrees_from_coo(g, by="src"))
    )
    rep = res.report
    assert [s.name for s in rep.stages] == [
        "degrees", "mapping", "relabel", "build_csr", "build_csc",
    ]
    assert rep.total_seconds > 0 and rep.total_modeled_bytes > 0
    assert all(s.modeled_bytes > 0 for s in rep.stages)
    # at least degree counting went through decide()
    assert any(d["kind"] == "reduce" for d in rep.decisions())
    d = rep.as_dict()
    assert d["variant"] == variant and len(d["stages"]) == 5


def test_pipeline_without_csc():
    res = PreprocessPipeline("identity", "baseline", with_csc=False).run(_graph())
    assert res.csc is None
    assert [s.name for s in res.report.stages][-1] == "build_csr"


@pytest.mark.parametrize("warmup", [True, False])
def test_stage_seconds_are_their_spans(warmup):
    spans.reset()
    res = PreprocessPipeline(
        "degree_sort", "auto", with_csc=False, warmup=warmup
    ).run(_graph())
    recorded = spans.recorded()
    (root,) = [s for s in recorded if s.name == "preprocess.run"]
    assert root.parent_id is None
    assert root.attrs == {
        "variant": "degree_sort", "build_method": "auto",
        "num_nodes": 512, "num_edges": res.report.num_edges,
    }
    by_name = {s.name: s for s in recorded}
    for stage in res.report.stages:
        sp = by_name[f"preprocess.{stage.name}"]
        assert stage.seconds == sp.seconds
        assert sp.parent_id == root.span_id == sp.root_id
        assert sp.attrs["stage"] == stage.name
        assert sp.attrs["decisions"] == ";".join(
            f"{d['method']}@r{d['bin_range']}" for d in stage.decisions
        )
        warm = by_name.get(f"preprocess.{stage.name}.warmup")
        if warmup:
            assert stage.warmup_seconds == warm.seconds > 0
        else:
            assert warm is None and stage.warmup_seconds == 0.0
    assert res.report.total_warmup_seconds == sum(
        s.warmup_seconds for s in res.report.stages
    )
    assert "warmup_seconds" in res.report.as_dict()["stages"][0]


def test_slack_stage_spans_split_fetch_and_layout():
    spans.reset()
    res = PreprocessPipeline(
        "degree_sort", "auto", with_csc=False, warmup=False, slack_headroom=0.25
    ).run(_graph())
    recorded = spans.recorded()
    (slack,) = [s for s in recorded if s.name == "preprocess.slack"]
    children = {s.name: s for s in recorded if s.parent_id == slack.span_id}
    assert set(children) == {"slack_csr.fetch", "slack_csr.layout"}
    fetch, layout = children["slack_csr.fetch"], children["slack_csr.layout"]
    assert slack.start_ns <= fetch.start_ns <= fetch.end_ns <= layout.start_ns
    assert layout.end_ns <= slack.end_ns
    assert fetch.seconds + layout.seconds <= slack.seconds
    assert res.report.stage("slack").seconds == slack.seconds
    # the neighbours stay on the device: only the n + 1 int32 offsets come back
    assert fetch.attrs == {"fetch_bytes": (res.csr.num_nodes + 1) * 4}
    s = res.slack
    # the upload is the per-vertex slack table: one int32 per vertex
    assert layout.attrs == {"slots": s.capacity, "upload_bytes": 4 * s.num_nodes}


def test_pipeline_sharded_8dev():
    """Mesh pipeline: degree counting + both builds through the sharded
    paths, equal to the single-device result."""
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.core import (PreprocessPipeline, build_csr_oracle,
                                csr_equal_as_sets, make_stream_mesh,
                                transpose_coo)
        from repro.core.graph import gen_uniform
        from repro.core.reorder import relabel_coo

        assert jax.device_count() == 8
        g = gen_uniform(300, 4, seed=5)
        res = PreprocessPipeline(
            variant="degree_sort", mesh=make_stream_mesh(8)).run(g)
        assert res.report.sharded and res.report.build_method == "sharded"
        rel = relabel_coo(g, res.new_ids)
        assert csr_equal_as_sets(res.csr, build_csr_oracle(rel))
        assert csr_equal_as_sets(res.csc, build_csr_oracle(transpose_coo(rel)))
        print("ok")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"


def test_amortization_iters():
    assert amortization_iters(1.0, 0.3, 0.1) == pytest.approx(5.0)
    assert amortization_iters(1.0, 0.1, 0.3) == float("inf")
    assert amortization_iters(1.0, 0.1, 0.1) == float("inf")


# -- fused legality regression (no hardcoded method="fused" in core/) ------


def test_degree_count_respects_fused_legality(tmp_path):
    """Regression: degree counting used to force method="fused"
    regardless of ``fused_fits``. With a hardware model whose fast level
    cannot hold the accumulator, the executor must decide a two-phase
    method — and the counts must still be right."""
    tiny = HardwareModel(
        name="tiny-cache",
        fast_levels=(256,),  # 256 B: a 512-vertex int32 histogram never fits
        cbuffer_bytes=64,
        dram_bandwidth=60e9,
        fast_bandwidth=1e12,
    )
    # fresh cache dir: a persisted autotune entry must not preempt the
    # analytic legality decision under test
    ex = PBExecutor(hw=tiny, cache_dir=str(tmp_path))
    assert not ex.fused_fits(512)
    prev = get_default_executor()
    set_default_executor(ex)
    try:
        g = gen_uniform(512, 16, seed=41)  # stream above _SORT_THRESHOLD
        res = PreprocessPipeline("degree_sort", "pb", bin_range=64).run(g)
        reduce_methods = {
            d["method"] for d in ex.decision_log if d["kind"] == "reduce"
        }
        assert reduce_methods and "fused" not in reduce_methods
        np.testing.assert_array_equal(
            np.asarray(res.degrees), np.asarray(degrees_from_coo(g, by="src"))
        )
        assert csr_equal_as_sets(
            res.csr, build_csr_oracle(relabel_coo(g, res.new_ids))
        )
    finally:
        set_default_executor(prev)


def test_degree_count_uses_fused_when_legal(tmp_path):
    """The flip side: on the default hardware model a smoke-sized degree
    count IS fused (the analytic reduce tree picks the single sweep)."""
    ex = PBExecutor(cache_dir=str(tmp_path))  # fresh log, empty cache
    prev = get_default_executor()
    set_default_executor(ex)
    try:
        g = gen_uniform(512, 16, seed=42)
        PreprocessPipeline("degree_sort", "pb", bin_range=64).run(g)
        assert any(
            d["kind"] == "reduce" and d["method"] == "fused"
            for d in ex.decision_log
        )
    finally:
        set_default_executor(prev)


# -- vectorized csr_equal_as_sets ------------------------------------------


def _csr(offsets, neighs, n):
    return CSR(
        jnp.asarray(offsets, jnp.int32), jnp.asarray(neighs, jnp.int32), n
    )


def test_csr_equal_as_sets_vectorized():
    a = _csr([0, 2, 4], [1, 0, 0, 1], 2)
    same_sets = _csr([0, 2, 4], [0, 1, 1, 0], 2)  # permuted within vertices
    cross = _csr([0, 2, 4], [0, 0, 1, 1], 2)  # multiset moved across vertices
    diff_off = _csr([0, 1, 4], [1, 0, 0, 1], 2)
    assert csr_equal_as_sets(a, same_sets)
    assert not csr_equal_as_sets(a, cross)
    assert not csr_equal_as_sets(a, diff_off)


def test_csr_equal_as_sets_matches_build_variants():
    g = _graph(seed=51)
    a = build_csr(g, method="baseline")
    b = build_csr(g, method="pb", bin_range=64)
    assert csr_equal_as_sets(a, b)
    # flipping one neighbor breaks it
    bad = np.asarray(b.neighs).copy()
    bad[0] = (bad[0] + 1) % g.num_nodes
    assert not csr_equal_as_sets(a, _csr(np.asarray(b.offsets), bad, g.num_nodes))
