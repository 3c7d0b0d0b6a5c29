"""Each job kind at a tiny size against its plain reference, through the
harness's run (the look for a chip skipped); its control, and faults
planted in the timed path, must come out not correct."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, run
from bench.jobs import pagerank as pr_job
from bench.jobs import preprocess as pre_job

SPEC = run.load_spec()
REAL_PAGERANK = pr_job.pagerank_fused
SEED = 2**31 + 4099
KRON = dict(name="kron22", generator="kron", scale=10, num_nodes=1024, num_edges=1 << 14,
            num_arcs=1 << 15, a=0.57, b=0.19, c=0.19)
CELLS = {"kron22-pagerank": KRON, "kron22-build": KRON}


def run_small(cell, seconds=0.2):
    return run.run_cell(SPEC, cell, SEED, seconds, False, jax.devices(), config=CELLS[cell],
                        t_start=time.perf_counter())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_runs_correct_at_small_size(cell):
    res = run_small(cell)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert list(res)[-1] == "checks"
    assert res["metrics"]["setup_s"]["value"] > 0
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], name
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_fails_its_limits(cell):
    limits = run.job_module(run.cell_files(SPEC, cell)[2]["job"]).LIMITS
    got = list(control.readings(cell, [SEED], [SEED, SEED + 1], config=CELLS[cell]))
    program = [n for k, _, n in got if k == "program"]
    controls = [n for k, _, n in got if k == "control"]
    assert all(all(v <= limits[k] for k, v in n.items()) for n in program)
    assert all(any(v > limits[k] for k, v in n.items()) for n in controls)


# -- faults planted where the answer is produced ------------------------------


def pr_state_unchanged(coo, iters=10, method=None):
    """A step that returns its state unchanged: the uniform start."""
    return _Ranks(jnp.full((coo.num_nodes,), 1.0 / coo.num_nodes, jnp.float32))


class _Ranks:
    def __init__(self, ranks):
        self.ranks = ranks


def pr_half_batch(coo, iters=10, method=None):
    """Half of the edges left out, the iteration run over the rest."""
    half = coo._replace(src=coo.src[: coo.num_edges // 2], dst=coo.dst[: coo.num_edges // 2])
    return REAL_PAGERANK(half, iters=iters)


def pr_altered(coo, iters=10, method=None):
    """One rank altered by 1% where it is produced."""
    r = REAL_PAGERANK(coo, iters=iters).ranks
    return _Ranks(r.at[7].multiply(1.01))


@pytest.mark.parametrize("fault", [pr_state_unchanged, pr_half_batch, pr_altered])
def test_pagerank_fault_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(pr_job, "pagerank_fused", fault)
    res = run_small("kron22-pagerank")
    assert res["correct"] is False and res["failed"] >= 1


class _Faulty:
    """The real pipeline, with its result broken by ``how``."""

    real = pre_job.PreprocessPipeline

    def __init__(self, how, **kw):
        self.how, self.pipe = how, self.real(**kw)

    def run(self, coo):
        if self.how == "half":
            coo = coo._replace(src=coo.src[: coo.num_edges // 2], dst=coo.dst[: coo.num_edges // 2])
        res = self.pipe.run(coo)
        if self.how == "unchanged":  # the edge list handed back, never relabelled
            res = res._replace(new_ids=jnp.arange(coo.num_nodes, dtype=jnp.int32))
        if self.how == "altered":
            res = res._replace(csr=res.csr._replace(neighs=res.csr.neighs.at[3].add(1)))
        return res


@pytest.mark.parametrize("how", ["unchanged", "half", "altered"])
def test_preprocess_fault_is_not_correct(monkeypatch, how):
    monkeypatch.setattr(pre_job, "PreprocessPipeline", lambda **kw: _Faulty(how, **kw))
    res = run_small("kron22-build")
    assert res["correct"] is False and res["failed"] == 1


def test_reservoir_keeps_a_seeded_sample():
    class Fake:
        num_edges, num_nodes = 1, 1

    kept = []
    for seed in (1, 2):
        job = pr_job.Job(Fake(), {"iters": 1}, np.random.default_rng(seed))
        for i in range(50):
            job.keep(i)
        kept.append(list(job.kept))
        assert len(job.kept) == pr_job.KEEP
    again = pr_job.Job(Fake(), {"iters": 1}, np.random.default_rng(1))
    for i in range(50):
        again.keep(i)
    assert again.kept == kept[0]
