"""The graph of a cell with more than one chip, made on all of them, on
four host devices. The checks run in one subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``, so that this
process keeps its single CPU device; each test reads its part."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench.jobs import pagerank as pr_job

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEEDS = (12345, 2**32 + 2**31 + 7)  # the second needs the seed's high word
with open(os.path.join(ROOT, "bench", "traffic", "pagerank.json")) as f:
    ITERS = json.load(f)["iters"]

CHECKS = """
import json, time
import types

import jax
import numpy as np

from bench import gen, run
from bench.reference import pagerank as ref
from repro.core.pagerank import pagerank_sharded

KRON = dict(name="kron12", generator="kron", scale=12, num_nodes=4096, num_edges=1 << 16,
            num_arcs=1 << 17, a=0.57, b=0.19, c=0.19)
# three chips: chip 1's share holds forward and reversed arcs
KRON3 = dict(KRON, num_edges=3 << 14, num_arcs=6 << 14)
devices = jax.devices()
out = {"devices": len(devices)}


def host(coo):
    return np.asarray(coo.src), np.asarray(coo.dst)


def same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


for seed in SEEDS:
    one = host(gen.generate(KRON, gen.seed_key(seed)))
    out[f"equal.4.{seed}"] = same(one, host(gen.generate(KRON, gen.seed_key(seed), devices)))
one3 = host(gen.generate(KRON3, gen.seed_key(SEEDS[0])))
out["equal.3"] = same(one3, host(gen.generate(KRON3, gen.seed_key(SEEDS[0]), devices[:3])))

one = host(gen.generate(KRON, gen.seed_key(SEEDS[0])))
coo = gen.generate(KRON, gen.seed_key(SEEDS[0]), devices)
shards = []
for arr, want in zip((coo.src, coo.dst), one):
    for s in arr.addressable_shards:
        sl = s.index[0]
        shards.append([devices.index(s.device), sl.start, sl.stop,
                       bool(np.array_equal(np.asarray(s.data), want[sl]))])
out["shards"] = shards
out["spec"] = [str(coo.src.sharding.spec), str(coo.dst.sharding.spec)]

try:
    gen.generate(dict(KRON, num_edges=(1 << 14) + 1, num_arcs=(2 << 14) + 2), gen.seed_key(1), devices)
    out["indivisible"] = "no error"
except ValueError as e:
    out["indivisible"] = "ValueError: " + str(e)

mesh = coo.src.sharding.mesh
ranks = np.asarray(pagerank_sharded(coo, mesh=mesh, iters=ITERS).ranks)
out["pagerank_mesh"] = dict(mesh.shape)
out["pagerank_err"] = ref.max_rel_err(ranks, ref.ranks(*one, KRON["num_nodes"], ITERS))

seen = {}


class Job:
    work = 1

    def __init__(self, coo, traffic, rng):
        seen["spec"] = str(coo.src.sharding.spec)
        seen["mesh"] = dict(coo.src.sharding.mesh.shape)
        seen["devices"] = sorted(devices.index(d) for d in coo.src.sharding.device_set)
        seen["shard_len"] = sorted({s.data.shape[0] for s in coo.src.addressable_shards})

    def counts(self):
        return {}

    def run(self):
        return 0

    def readings(self, out):
        return {}

    def keep(self, out):
        pass

    def fetch(self):
        return []


def check(kept, src, dst, config, traffic):
    seen["host_copies"] = same((src, dst), one)
    return {"nothing": (0.0, 0.0)}, 0


spec = run.load_spec()
spec["workloads"] = [dict(w, chips=4) for w in spec["workloads"]]
run.job_module = lambda kind: types.SimpleNamespace(Job=Job, check=check)
res = run.run_cell(spec, "kron22-pagerank", SEEDS[0], 0.01, False, devices, config=KRON,
                   t_start=time.perf_counter())
out["run_cell"] = dict(seen, correct=res["correct"], count=res["device"]["count"])
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def found():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    code = f"SEEDS = {SEEDS!r}\nITERS = {ITERS!r}\n" + textwrap.dedent(CHECKS)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


@pytest.mark.parametrize("case", [f"equal.4.{s}" for s in SEEDS] + ["equal.3"],
                         ids=["4-chips", "4-chips-high-seed", "3-chips-split-tuples"])
def test_sharded_graph_equals_the_one_device_graph(found, case):
    assert found[case] is True


def test_each_device_holds_its_contiguous_share(found):
    quarter = (1 << 17) // 4
    want = [[k, k * quarter, (k + 1) * quarter, True] for k in range(4)]
    assert found["shards"] == want + want  # src, then dst
    assert found["spec"] == ["PartitionSpec('shard',)"] * 2


def test_arcs_that_the_chips_do_not_divide_raise(found):
    assert found["indivisible"].startswith("ValueError: ")


def test_pagerank_sharded_over_the_generated_arcs_matches_the_reference(found):
    """The program's sharded path takes the arcs as they are made, with
    the mesh read from them, within the ``kron22-pagerank`` limit."""
    assert found["pagerank_mesh"] == {"shard": 4}
    assert found["pagerank_err"] <= pr_job.LIMITS["rank_max_rel_err"]


def test_run_cell_hands_its_job_arcs_sharded_over_its_chips(found):
    cell = found["run_cell"]
    assert cell["spec"] == "PartitionSpec('shard',)" and cell["mesh"] == {"shard": 4}
    assert cell["devices"] == [0, 1, 2, 3] and cell["shard_len"] == [(1 << 17) // 4]
    assert cell["host_copies"] is True  # the check's host copies gather every shard
    assert cell["correct"] is True and cell["count"] == 4
