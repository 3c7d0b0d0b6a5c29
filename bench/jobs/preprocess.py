"""Preprocessing job: ``PreprocessPipeline(...).run(coo)`` exactly as
``GraphFrontend.register_graph`` runs it (degree count, degree-sort
relabel, PB CSR rebuild, slack layout), each stage once per job.

The rate counts input arcs per job. Jobs hold no earlier job's output:
one result is kept, drawn uniformly over the window's jobs from the seed
(a reservoir of one), and compared exactly with the numpy reference once
the window has closed. The relabelled edge list is checked through the
CSR, which holds every relabelled edge in edge-list order per vertex.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core.preprocess import PreprocessPipeline

from bench.reference import preprocess as ref

# Exact comparisons: any differing entry is a fault.
LIMITS = {
    "degrees_mismatch": 0,
    "new_ids_mismatch": 0,
    "csr_offsets_mismatch": 0,
    "csr_neighs_mismatch": 0,
    "slack_mismatch": 0,
}


class Job:
    def __init__(self, coo, traffic: dict, rng: np.random.Generator):
        self.coo = coo
        self.rng = rng
        self.work = coo.num_edges
        self.pipe = PreprocessPipeline(
            variant=traffic["variant"],
            build_method=traffic["build_method"],
            with_csc=bool(traffic["with_csc"]),
            slack_headroom=float(traffic["slack_headroom"]),
            slack_min_slack=int(traffic["slack_min_slack"]),
            warmup=False,
        )
        self.seen = 0
        self.kept = None

    def counts(self) -> dict:
        return {"num_nodes": self.coo.num_nodes, "num_edges": self.coo.num_edges}

    def run(self):
        res = self.pipe.run(self.coo)
        jax.block_until_ready((res.csr, res.new_ids, res.degrees, res.slack))
        return res

    def readings(self, res) -> dict:
        """The program's own synchronized stage times of this job."""
        return {f"stage.{s.name}": s.seconds for s in res.report.stages}

    def keep(self, res) -> None:
        self.seen += 1
        if self.rng.random() * self.seen < 1.0:
            self.kept = res

    def fetch(self) -> list:
        res, self.kept, self.coo, self.pipe = self.kept, None, None, None
        if res is None:
            return []
        return [{
            "degrees": np.asarray(res.degrees),
            "new_ids": np.asarray(res.new_ids),
            "offsets": np.asarray(res.csr.offsets),
            "neighs": np.asarray(res.csr.neighs),
            "slack_offsets": np.asarray(res.slack.offsets),
            "slack_neighs": np.asarray(res.slack.neighs),
            "slack_counts": np.asarray(res.slack.counts),
        }]


def reference(src: np.ndarray, dst: np.ndarray, config: dict, traffic: dict, control: bool = False) -> dict:
    """The reference's version of a kept result; ``control`` builds the
    CSR with each vertex's neighbours sorted by value instead."""
    n = int(config["num_nodes"])
    deg = ref.degrees(src, n)
    ids = ref.new_ids(deg)
    build = ref.csr_value_sorted if control else ref.csr
    offsets, neighs = build(ids[src], ids[dst], n)
    s_off, s_nei, s_cnt = ref.slack(
        offsets, neighs, float(traffic["slack_headroom"]), int(traffic["slack_min_slack"])
    )
    return {
        "degrees": deg, "new_ids": ids, "offsets": offsets, "neighs": neighs,
        "slack_offsets": s_off, "slack_neighs": s_nei, "slack_counts": s_cnt,
    }


def compare(got: dict, want: dict) -> dict:
    slack = sum(
        ref.mismatches(got[k], want[k]) for k in ("slack_offsets", "slack_neighs", "slack_counts")
    )
    numbers = {
        "degrees_mismatch": ref.mismatches(got["degrees"], want["degrees"]),
        "new_ids_mismatch": ref.mismatches(got["new_ids"], want["new_ids"]),
        "csr_offsets_mismatch": ref.mismatches(got["offsets"], want["offsets"]),
        "csr_neighs_mismatch": ref.mismatches(got["neighs"], want["neighs"]),
        "slack_mismatch": slack,
    }
    return {k: (v, LIMITS[k]) for k, v in numbers.items()}


def check(kept: list, src: np.ndarray, dst: np.ndarray, config: dict, traffic: dict) -> tuple:
    """(numbers compared, failed jobs) for the one kept result."""
    if not kept:
        return {k: (float("inf"), v) for k, v in LIMITS.items()}, 1
    numbers = compare(kept[0], reference(src, dst, config, traffic))
    return numbers, int(any(v > lim for v, lim in numbers.values()))


def control(src_dev, dst_dev, src, dst, config: dict, traffic: dict) -> dict:
    """The value-sorted reference in the program's place."""
    return compare(reference(src, dst, config, traffic, control=True), reference(src, dst, config, traffic))
