"""Per-layer metric readers on hand-made inputs with known answers."""
import pytest

from bench import run, trace

KERNEL = 'custom_call_target="tpu_custom_call"'


def ctx(**kw):
    base = {
        "trace": trace.Summary(
            window_s=10.0, busy_s=8.0, devices=1,
            op_seconds={f"%f.1 = f32[8] custom-call(), {KERNEL}": 4.0, "%fusion.2 = f32[8] fusion()": 4.0},
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


def test_idle_shares():
    for name in ("idle_share.analytics", "idle_share.build"):
        assert run.metric_reader(name)(ctx()) == pytest.approx(20.0)


def test_stage_means_over_the_window_jobs():
    readings = [{"stage.build_csr": 10.0, "stage.degrees": 3.0}, {"stage.build_csr": 12.0, "stage.degrees": 3.5}]
    assert run.metric_reader("preprocess.build_csr_s")(ctx(readings=readings)) == pytest.approx(11.0)
    assert run.metric_reader("preprocess.degrees_s")(ctx(readings=readings)) == pytest.approx(3.25)
    assert run.metric_reader("preprocess.degrees_s")(ctx()) is None
