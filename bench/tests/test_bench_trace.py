"""The trace reducer: busy union, time per operation and gap naming, on a
hand-written trace with known answers and on a small trace recorded on a
v5e (``data/v5e_small.xplane.pb``)."""
import os

import pytest
from jax.profiler import ProfileData

from bench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")


def xspace(device_ops, host_spans) -> ProfileData:
    """A trace of one TPU with ``device_ops`` [(name, start_ns, end_ns)] on
    its ``XLA Ops`` line and ``host_spans`` on one host thread."""

    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = " ".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} duration_ps: {(e - s) * 1000} }}"
            for n, s, e in events
        )
        meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for n, i in ids.items())
        return f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}'

    return ProfileData.from_text_proto(
        plane(1, "/device:TPU:0", "XLA Ops", device_ops) + plane(2, "/host:CPU", "python", host_spans)
    )


# device: a loop [10,40] holding a [12,30] and b [30,38]; a [60,70]; c [95,120] (past the window)
OPS = [("loop", 10, 40), ("a", 12, 30), ("b", 30, 38), ("a", 60, 70), ("c", 95, 120)]
# host: window [0,100]; a job [0,58] with a host call [40,58]; a wait [58,100]
SPANS = [("bench.traced", 0, 100), ("bench.job", 0, 58), ("$graph.py:1 from_csr", 40, 58),
         ("bench.wait", 58, 100)]


def test_busy_union_op_time_and_gaps_by_hand():
    s = trace.summarize(xspace(OPS, SPANS))
    assert s.window_s == pytest.approx(100e-9)
    # union inside the window: [10,40] + [60,70] + [95,100] = 45 ns
    assert s.busy_s == pytest.approx(45e-9)
    assert s.idle_share() == pytest.approx(0.55)
    # self time: the loop less its body, a twice, c up to the window's end
    assert s.op_seconds == pytest.approx({"loop": 4e-9, "a": 28e-9, "b": 8e-9, "c": 5e-9})
    # gaps [0,10] in the job, [40,60] in the host call, [70,95] in the wait
    assert [n for n, _ in s.gaps] == ["bench.job", "$graph.py:1 from_csr", "bench.wait"]
    assert [g for _, g in s.gaps] == pytest.approx([10e-9, 20e-9, 25e-9])
    assert [n for n, _ in s.top_gaps()] == ["bench.wait", "$graph.py:1 from_csr", "bench.job"]
    assert s.kernel_seconds("a") == pytest.approx(28e-9)


def test_gap_named_by_innermost_span_at_its_midpoint():
    spans = [("outer", 0, 100), ("inner", 20, 30), ("deeper", 22, 28), ("later", 60, 90)]
    gaps = [(21, 29), (10, 15), (70, 80), (95, 99), (200, 210)]
    assert trace.name_gaps(gaps, spans) == ["deeper", "outer", "later", "outer", "untraced"]


def test_merge_and_idle_intervals():
    assert trace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    assert trace.idle_intervals([[1, 4], [5, 8]], 0, 10) == [(0, 1), (4, 5), (8, 10)]


def test_short_op_names():
    text = ('%f.1 = s32[2048,16,128]{2,1,0:T(8,128)} custom-call(s32[64]{0} %idx.1), '
            'custom_call_target="tpu_custom_call"')
    assert trace.short_op_name(text) == "%f.1 custom-call[tpu_custom_call]"
    assert trace.short_op_name("%while.2 = (s32[]{:T(128)}, s32[91]{0}) while(x)") == "%while.2 while"


def test_no_window_span_or_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize(xspace(OPS, [("bench.job", 0, 50)]))
    with pytest.raises(ValueError):
        trace.summarize(xspace([], SPANS))


def test_recorded_v5e_trace():
    """A fused reduce (the Pallas kernel), a cumsum and a 20 ms host sleep
    between two jobs, traced on one v5e with the Python tracer on."""
    s = trace.reduce(FIXTURE)
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.028587328, rel=1e-6)
    assert s.busy_s == pytest.approx(0.006194422, rel=1e-6)
    assert 0 < s.busy_s < s.window_s
    # every op is on one line with no nesting: self time sums to at least
    # the busy union
    assert sum(s.op_seconds.values()) >= s.busy_s * (1 - 1e-9)
    kernel = s.kernel_seconds("%_lambda_.1 ")
    assert kernel == pytest.approx(0.006187654, rel=1e-6)
    assert s.top_ops(1)[0][0] == "%_lambda_.1 custom-call[tpu_custom_call]"
    name, secs = s.top_gaps(1)[0]
    assert name == "$time sleep" and secs == pytest.approx(0.021099813, rel=1e-6)
    assert sum(g for _, g in s.gaps) == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
