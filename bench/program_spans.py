"""Per-job sums of the program's own spans (``repro.core.spans``), for
the per-layer metrics whose source is ``program_span``.

The program keeps its closed spans in memory; a preprocessing job is one
root span ``preprocess.run`` and every span under it shares its
``root_id``. ``bench/run.py`` makes one reading per window job, and the
window's jobs are the last the run makes, so the window's roots are the
last ``len(ctx["readings"])`` of them: the warm job and anything before
it come earlier.
"""
JOB = "preprocess.run"


def recorded():
    """The program's recorded spans; None for a program that has none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.recorded()


def seconds_per_job(ctx, name: str, job: str = JOB):
    """Seconds of the spans called ``name`` under each of the window's
    ``job`` roots, summed per job and averaged over the jobs; None when
    there is nothing to read or a window job has no such span."""
    jobs = len(ctx["readings"])
    found = recorded() if jobs else None
    if found is None:
        return None
    roots = [s for s in found if s.name == job and s.parent_id is None][-jobs:]
    if len(roots) < jobs:
        return None
    per_job = {r.span_id: [] for r in roots}
    for s in found:
        if s.name == name and s.root_id in per_job:
            per_job[s.root_id].append(s.seconds)
    if not all(per_job.values()):
        return None
    return sum(sum(v) for v in per_job.values()) / jobs
