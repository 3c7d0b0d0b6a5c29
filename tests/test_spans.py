"""Program spans and counters (core/spans.py): nesting and the ids that
tie a job's spans together, attrs and counters, the bounded buffer, no
span while JAX traces, and the spans as host events of a profiler trace.
"""
import glob
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.core import spans
from repro.core.executor import HostStreamStats


@pytest.fixture(autouse=True)
def fresh():
    spans.reset()
    yield
    spans.reset()


def by_name():
    return {s.name: s for s in spans.recorded()}


def test_nesting_parent_and_root_ids():
    with spans.span("job"):
        with spans.span("stage"):
            with spans.span("step"):
                pass
        with spans.span("other"):
            pass
    with spans.span("next_job"):
        pass
    got = by_name()
    assert [s.name for s in spans.recorded()] == ["step", "stage", "other", "job", "next_job"]
    job = got["job"]
    assert job.parent_id is None and job.root_id == job.span_id
    assert got["stage"].parent_id == job.span_id
    assert got["step"].parent_id == got["stage"].span_id
    assert got["other"].parent_id == job.span_id
    assert {got[n].root_id for n in ("stage", "step", "other")} == {job.span_id}
    assert got["next_job"].parent_id is None
    assert got["next_job"].root_id == got["next_job"].span_id != job.span_id
    assert len({s.span_id for s in spans.recorded()}) == 5


def test_times_nest_and_match_the_open_span():
    with spans.span("outer") as outer:
        with spans.span("inner") as inner:
            pass
    got = by_name()
    assert got["outer"].start_ns <= got["inner"].start_ns <= got["inner"].end_ns <= got["outer"].end_ns
    assert got["outer"].seconds == outer.seconds > 0
    assert got["inner"].seconds == inner.seconds


def test_attrs_counters_and_totals():
    with spans.span("a", kind="x", n=3) as sp:
        spans.count("bytes", 10)
        with spans.span("b"):
            spans.count("bytes", 5)
            spans.count("levels")
        spans.count("bytes", 1)
        sp.set(decided="fused@r2048")
    spans.count("bytes", 100)  # no span open: the totals alone
    got = by_name()
    assert got["a"].attrs == {"kind": "x", "n": 3, "decided": "fused@r2048", "bytes": 11}
    assert got["b"].attrs == {"bytes": 5, "levels": 1}
    assert spans.totals() == {"bytes": 116, "levels": 1}
    spans.reset()
    assert spans.recorded() == [] and spans.totals() == {}


def test_buffer_keeps_the_newest():
    for i in range(spans.CAPACITY + 10):
        with spans.span("s", i=i):
            pass
    kept = spans.recorded()
    assert len(kept) == spans.CAPACITY
    assert kept[0].attrs["i"] == 10 and kept[-1].attrs["i"] == spans.CAPACITY + 9


def test_no_span_while_jax_traces():
    @jax.jit
    def f(x):
        with spans.span("traced", over=(x,)):
            spans.count("seen")
            return x + 1

    with spans.span("outer"):
        f(jnp.ones(4)).block_until_ready()
    assert [s.name for s in spans.recorded()] == ["outer"]
    # what the traced body counted is not charged to the span around it
    assert by_name()["outer"].attrs == {}


def test_threads_keep_their_own_stacks():
    def job(name):
        with spans.span(name):
            with spans.span(name + ".child"):
                pass

    threads = [threading.Thread(target=job, args=(f"t{i}",)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    got = by_name()
    for i in range(4):
        root, child = got[f"t{i}"], got[f"t{i}.child"]
        assert root.parent_id is None
        assert child.parent_id == root.span_id == child.root_id


def test_host_stream_stats_read_their_spans():
    import numpy as np

    hs = HostStreamStats()
    with hs.timed():
        spans.count("levels")
    hs.upload(np.zeros(8, np.int32))
    assert hs.levels == 1 and hs.upload_bytes == 32 and hs.seconds > 0
    streams = [s for s in spans.recorded() if s.name == "traversal.host_stream"]
    assert len(streams) == 2
    assert hs.seconds == pytest.approx(sum(s.seconds for s in streams))
    hs.reset()
    assert (hs.seconds, hs.upload_bytes, hs.levels) == (0.0, 0, 0)


def test_spans_are_host_events_of_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("job.root", variant="degree_sort", n=7) as sp:
            with spans.span("job.stage", stage="build, csr=1#"):
                spans.count("bytes", 42)
                jnp.ones(16).block_until_ready()
            sp.set(decisions="fused@r2048;counting@r46340")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("job."):
                        events[ev.name] = (line.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
    assert set(events) == {"job.root", "job.stage"}
    root_line, root_start, root_dur, root_args = events["job.root"]
    stage_line, stage_start, stage_dur, stage_args = events["job.stage"]
    assert root_line == stage_line  # one thread
    assert root_start <= stage_start and stage_start + stage_dur <= root_start + root_dur
    assert root_args == {"variant": "degree_sort", "n": 7, "decisions": "fused@r2048;counting@r46340"}
    # characters the trace's arg encoding reserves are replaced
    assert stage_args == {"stage": "build; csr:1_", "bytes": 42}
    # the in-memory record keeps the attrs as given
    assert by_name()["job.stage"].attrs == {"stage": "build, csr=1#", "bytes": 42}
