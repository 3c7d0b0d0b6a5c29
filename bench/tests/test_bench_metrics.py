"""Per-layer metric readers on hand-made inputs with known answers."""
import pytest

from bench import run, trace

MOSAIC = 'custom_call_target="tpu_custom_call"'
FUSED = f"%pb_fused_reduce.3 = f32[8] custom-call(), {MOSAIC}"


def ctx(**kw):
    base = {
        "trace": trace.Summary(
            window_s=10.0, busy_s=8.0, devices=1,
            op_seconds={FUSED: 4.0, "%fusion.2 = f32[8] fusion()": 4.0},
            gaps=[("bench.job", 2.0)],
        ),
        "counts": {"num_nodes": 1000, "num_edges": 10_000, "iterations": 5, "tuples": 50_000},
        "readings": [], "traced_jobs": 2, "device_kind": "TPU v5 lite",
    }
    base.update(kw)
    return base


def test_roofline_share_counts_bytes_of_the_traced_iterations():
    # 2 jobs x 5 iterations x (10,000 x 12 + 1,000 x 12) B over 819 GB/s x 8 s
    want = 100.0 * 10 * 132_000 / (819e9 * 8.0)
    assert run.metric_reader("pagerank.roofline_share")(ctx()) == pytest.approx(want)


def test_fused_ns_per_tuple_and_its_absence():
    read = run.metric_reader("fused.ns_per_tuple")
    assert read(ctx()) == pytest.approx(4.0e9 / (2 * 50_000))
    no_kernel = ctx(trace=trace.Summary(10.0, 8.0, 1, {"%fusion.2 = f32[8] fusion()": 8.0}, []))
    assert read(no_kernel) is None


def test_fused_ns_per_tuple_counts_the_fused_kernel_alone():
    """Other Mosaic kernels, the row kernel among them, and operations
    that take the kernel's result as an operand are not its time."""
    ops = {
        FUSED: 4.0,
        f"%pb_fused_reduce_rows.1 = f32[8,128] custom-call(), {MOSAIC}": 1.0,
        f"%pb_counting_positions.2 = s32[8] custom-call(), {MOSAIC}": 1.0,
        f"%pb_binread_scatter_add.4 = f32[8] custom-call(), {MOSAIC}": 1.0,
        "%fusion.6 = f32[8] fusion(f32[8] %pb_fused_reduce.3), kind=kLoop": 1.0,
    }
    read = run.metric_reader("fused.ns_per_tuple")
    assert read(ctx(trace=trace.Summary(10.0, 9.0, 1, ops, []))) == pytest.approx(4.0e9 / (2 * 50_000))


def test_fused_ns_per_tuple_reads_the_kron22_pagerank_trace_as_before():
    """The device seconds of a traced ``kron22-pagerank`` job on a v5e
    (5 iterations over 134,217,728 arcs), where the fused kernel is the
    only Mosaic kernel: matching it by name reads what matching any
    Mosaic kernel read, 47.12 ns per tuple."""
    ops = {
        f"%pb_fused_reduce.3 = f32[2,16,128] custom-call(s32[134217728] %p.1), {MOSAIC}": 31.623734253,
        "%fusion.6 = f32[134217728] fusion(f32[4194304] %p.2), kind=kLoop": 5.775811145,
        "%fusion.1 = s32[4194304] fusion(s32[134217728] %p.1), kind=kLoop": 1.171776874,
        "%sort = s32[4194304] sort(s32[4194304] %p.3)": 0.372886002,
    }
    c = ctx(trace=trace.Summary(38.977, 38.9755, 1, ops, []), traced_jobs=1,
            counts={"num_nodes": 1 << 22, "num_edges": 1 << 27, "iterations": 5, "tuples": 5 << 27})
    got = run.metric_reader("fused.ns_per_tuple")(c)
    assert got == pytest.approx(47.12, abs=0.005)
    any_mosaic = sum(v for n, v in ops.items() if MOSAIC in n)
    assert got == pytest.approx(any_mosaic * 1e9 / (5 << 27))


def test_idle_shares():
    for name in ("idle_share.analytics", "idle_share.build"):
        assert run.metric_reader(name)(ctx()) == pytest.approx(20.0)


def test_stage_means_over_the_window_jobs():
    readings = [{"stage.build_csr": 10.0, "stage.degrees": 3.0}, {"stage.build_csr": 12.0, "stage.degrees": 3.5}]
    assert run.metric_reader("preprocess.build_csr_s")(ctx(readings=readings)) == pytest.approx(11.0)
    assert run.metric_reader("preprocess.degrees_s")(ctx(readings=readings)) == pytest.approx(3.25)
    assert run.metric_reader("preprocess.degrees_s")(ctx()) is None
