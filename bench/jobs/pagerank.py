"""PageRank job: ``repro.core.pagerank.pagerank_fused`` over all the
generated arcs, ``iters`` power iterations per call.

Every reduce goes through the executor's decision: the Pallas C-Buffer
kernel where the vertex domain fits its VMEM budget, another path where
it does not. The rate counts iterations x arcs. The ranks of up to
``KEEP`` jobs, drawn from the seed, are kept (4 B per vertex each) and
each is compared with the float64 reference once the window has closed.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core.pagerank import pagerank_fused

from bench.reference import pagerank as ref

# The widest relative gap of a job's ranks to the float64 reference that
# still counts as correct. Set between the program's readings and the
# bfloat16 control's (PERF.md, "Correctness limits").
LIMITS = {"rank_max_rel_err": 2e-4}
KEEP = 4


class Job:
    def __init__(self, coo, traffic: dict, rng: np.random.Generator):
        self.coo = coo
        self.iters = int(traffic["iters"])
        self.work = coo.num_edges * self.iters
        self.rng = rng
        self.seen = 0
        self.kept: list = []

    def counts(self) -> dict:
        """What one job does, for the per-layer metrics."""
        n, m = self.coo.num_nodes, self.coo.num_edges
        return {"num_nodes": n, "num_edges": m, "iterations": self.iters, "tuples": m * self.iters}

    def run(self):
        return jax.block_until_ready(pagerank_fused(self.coo, iters=self.iters).ranks)

    def readings(self, out) -> dict:
        return {}

    def keep(self, out) -> None:
        """A uniform sample of ``KEEP`` of the window's jobs, drawn from the
        seed (reservoir sampling): every job while there are fewer."""
        self.seen += 1
        if len(self.kept) < KEEP:
            self.kept.append(out)
        else:
            slot = int(self.rng.integers(self.seen))
            if slot < KEEP:
                self.kept[slot] = out

    def fetch(self) -> list:
        """Host copies of every kept result; the device copies are freed."""
        host = [np.asarray(r) for r in self.kept]
        self.kept.clear()
        self.coo = None
        return host


def check(kept: list, src: np.ndarray, dst: np.ndarray, config: dict, traffic: dict) -> tuple:
    """(numbers compared, failed jobs): the widest relative gap over all
    kept jobs, and how many jobs exceeded the limit."""
    want = ref.ranks(src, dst, int(config["num_nodes"]), int(traffic["iters"]))
    errs = [ref.max_rel_err(r, want) for r in kept]
    limit = LIMITS["rank_max_rel_err"]
    worst = max(errs) if errs else float("inf")
    return {"rank_max_rel_err": (worst, limit)}, sum(e > limit for e in errs)


def control(src_dev, dst_dev, src, dst, config: dict, traffic: dict) -> dict:
    """The bfloat16 reference in the program's place, judged as a job."""
    n, iters = int(config["num_nodes"]), int(traffic["iters"])
    got = ref.ranks_bf16(src_dev, dst_dev, n, iters)
    return check([got], src, dst, config, traffic)[0]
