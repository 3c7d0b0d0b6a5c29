"""The kron24 cell's files and its per-layer metric
``counting.onehot_cells_per_tuple``, read from the ``pagerank.run`` spans
of real jobs at a tiny size: on the counting path (an executor whose fast
level holds no fused accumulator, as past the VMEM budget) and on the
fused one."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, run
from bench.jobs import pagerank as pr_job
from repro.core import executor as executor_mod
from repro.core.executor import PBExecutor
from repro.core.plan import HardwareModel

SPEC = run.load_spec()
CELL = "kron24-pagerank1"
SEED = 2**33 + 12345
SMALL = dict(name="kron24", generator="kron", scale=10, num_nodes=1024, num_edges=1 << 14,
             num_arcs=1 << 15, a=0.57, b=0.19, c=0.19)


@pytest.fixture
def executor(monkeypatch):
    """Installs a fresh default executor on ``hw`` for the test."""
    def use(hw):
        ex = PBExecutor(hw)
        monkeypatch.setattr(executor_mod, "_DEFAULT", ex)
        return ex
    return use


def past_vmem():
    """A v5e whose fast level holds no fused accumulator: every reduce
    takes the two-phase path, as kron24's 2^24 vertices do on the chip."""
    hw = HardwareModel.tpu_v5e()
    return HardwareModel(hw.name, (1 << 16,), hw.cbuffer_bytes, hw.dram_bandwidth, hw.fast_bandwidth)


def run_small(seconds=0.2):
    return run.run_cell(SPEC, CELL, SEED, seconds, False, jax.devices(), config=SMALL,
                        t_start=time.perf_counter())


def test_kron24_config_is_graph500_at_scale_24():
    cell, config, traffic = run.cell_files(SPEC, CELL)
    assert cell["chips"] == 1 and traffic == {**traffic, "job": "pagerank", "iters": 1}
    assert (config["scale"], config["edgefactor"], config["num_nodes"]) == (24, 16, 1 << 24)
    assert (config["num_edges"], config["num_arcs"]) == (1 << 28, 1 << 29)
    assert (config["a"], config["b"], config["c"], config["id_dtype"]) == (0.57, 0.19, 0.19, "int32")
    assert pr_job.LIMITS == {"rank_max_rel_err": 2e-4}


def test_kron24_decision_is_counting_past_the_vmem_budget():
    d = PBExecutor(HardwareModel.tpu_v5e()).decide(1 << 24, 1 << 29, jnp.float32, kind="reduce")
    assert (d.method, d.bin_range, d.num_bins) == ("counting", 92681, 182)


def test_counting_path_cell_runs_correct_and_reads_its_cells(executor):
    ex = executor(past_vmem())
    res = run_small()
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["rank_max_rel_err"]["value"] <= 2e-4
    d = [e for e in ex.decision_log if e["kind"] == "reduce"][-1]
    assert d["method"] == "counting"
    ctx = {"readings": [{}] * res["attempted"]}
    m = SMALL["num_arcs"]
    num_bins = -(-SMALL["num_nodes"] // d["bin_range"])
    want = num_bins * -(-m // ex.block) * ex.block / m
    assert run.metric_reader("counting.onehot_cells_per_tuple")(ctx) == pytest.approx(want)


def test_fused_path_reads_no_onehot_cells(executor):
    ex = executor(HardwareModel.tpu_v5e())
    res = run_small()
    assert res["correct"] is True
    assert [e for e in ex.decision_log if e["kind"] == "reduce"][-1]["method"] == "fused"
    ctx = {"readings": [{}] * res["attempted"]}
    assert run.metric_reader("counting.onehot_cells_per_tuple")(ctx) is None


def test_padded_stream_counts_its_padding(executor, monkeypatch):
    """A stream that does not fill its last block: the scan builds one-hot
    rows for the padding too."""
    from repro.core import spans
    from repro.core.spans import Span

    attrs = dict(method="counting", num_bins=7, block=2048, num_edges=5000)
    roots = [Span("pagerank.run", i, None, i, 0, 1, attrs) for i in (1, 2)]
    monkeypatch.setattr(spans, "recorded", lambda: roots)
    read = run.metric_reader("counting.onehot_cells_per_tuple")
    assert read({"readings": [{}, {}]}) == pytest.approx(7 * 6144 / 5000)
    # a window job without the attrs, as the program before them recorded it
    roots[1] = roots[1]._replace(attrs={"method": "counting", "bin_range": 3})
    assert read({"readings": [{}, {}]}) is None


def test_counting_path_control_fails_its_limit(executor):
    executor(past_vmem())
    got = list(control.readings(CELL, [SEED], [SEED + 1], config=SMALL))
    program = [n for k, _, n in got if k == "program"]
    controls = [n for k, _, n in got if k == "control"]
    assert all(n["rank_max_rel_err"] <= 2e-4 for n in program)
    assert all(n["rank_max_rel_err"] > 2e-4 for n in controls)
    assert np.isfinite([n["rank_max_rel_err"] for n in program + controls]).all()
