"""The readers of the program's spans (``bench/program_spans.py`` and the
``preprocess.slack_*_s`` metrics) on hand-built span lists."""
import sys

import pytest

from bench import run
from repro.core import spans
from repro.core.spans import Span

METRICS = {"preprocess.slack_layout_s": "slack_csr.layout", "preprocess.slack_fetch_s": "slack_csr.fetch"}
S = 1_000_000_000  # ns


def job(root_id, start, children):
    """The spans of one build job, children first as the program closes
    them: ``children`` maps a span name to its lengths in seconds."""
    out, t, sid = [], start, root_id
    for name, lengths in children.items():
        for length in lengths:
            sid += 1
            out.append(Span(name, sid, root_id, root_id, t, t + int(length * S), {}))
            t += int(length * S)
    out.append(Span("preprocess.run", root_id, None, root_id, start, t, {}))
    return out


def ctx(jobs):
    return {"readings": [{} for _ in range(jobs)]}


@pytest.fixture
def recorded(monkeypatch):
    """Stands the given spans in for what the program recorded."""
    def use(found):
        monkeypatch.setattr(spans, "recorded", lambda: list(found))
    return use


@pytest.mark.parametrize("metric,child", METRICS.items())
def test_mean_over_the_window_jobs_without_the_warm_job(recorded, metric, child):
    warm = job(100, 0, {"slack_csr.fetch": [9.0], "slack_csr.layout": [90.0]})
    first = job(200, 200 * S, {"slack_csr.fetch": [0.5], "slack_csr.layout": [14.0]})
    # a job whose stage ran twice sums its spans
    second = job(300, 400 * S, {"slack_csr.fetch": [0.25, 0.25], "slack_csr.layout": [10.0, 6.0]})
    other = [Span(child, 999, 998, 998, 0, 50 * S, {})]  # under another root
    recorded(warm + first + other + second)
    want = {"slack_csr.fetch": (0.5 + 0.5) / 2, "slack_csr.layout": (14.0 + 16.0) / 2}[child]
    assert run.metric_reader(metric)(ctx(2)) == pytest.approx(want)
    # one window job: only the last root counts
    assert run.metric_reader(metric)(ctx(1)) == pytest.approx({"slack_csr.fetch": 0.5, "slack_csr.layout": 16.0}[child])


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("case", ["no_readings", "fewer_roots", "no_child", "one_job_lacks_child", "nothing_recorded"])
def test_nothing_to_read_reads_none(recorded, metric, case):
    full = {"slack_csr.fetch": [0.5], "slack_csr.layout": [14.0]}
    jobs, found = {
        "no_readings": (0, job(1, 0, full)),
        "fewer_roots": (3, job(1, 0, full) + job(10, 100 * S, full)),
        "no_child": (1, job(1, 0, {"preprocess.build_csr": [20.0]})),
        "one_job_lacks_child": (2, job(1, 0, full) + job(10, 100 * S, {})),
        "nothing_recorded": (1, []),
    }[case]
    recorded(found)
    assert run.metric_reader(metric)(ctx(jobs)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_spans_reads_none(recorded, monkeypatch, metric):
    import repro.core

    recorded(job(1, 0, {"slack_csr.fetch": [0.5], "slack_csr.layout": [14.0]}))
    assert run.metric_reader(metric)(ctx(1)) is not None
    # as in a program that predates the spans: the import fails
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert run.metric_reader(metric)(ctx(1)) is None


def test_real_pipeline_spans_are_read():
    """The readers against the spans a small pipeline really records."""
    from repro.core.graph import gen_powerlaw
    from repro.core.preprocess import PreprocessPipeline

    spans.reset()
    pipe = PreprocessPipeline(with_csc=False, warmup=False, slack_headroom=0.25)
    g = gen_powerlaw(256, 4, seed=3)
    pipe.run(g)  # the warm job
    reports = [pipe.run(g).report for _ in range(2)]
    found = spans.recorded()
    layout = [s.seconds for s in found if s.name == "slack_csr.layout"][-2:]
    fetch = [s.seconds for s in found if s.name == "slack_csr.fetch"][-2:]
    assert run.metric_reader("preprocess.slack_layout_s")(ctx(2)) == pytest.approx(sum(layout) / 2)
    assert run.metric_reader("preprocess.slack_fetch_s")(ctx(2)) == pytest.approx(sum(fetch) / 2)
    for r, lay, fet in zip(reports, layout, fetch):
        assert lay + fet <= r.stage("slack").seconds
