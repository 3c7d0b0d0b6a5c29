"""Fig 2: pre-processing is a large share of end-to-end time — now told
end-to-end by the PreprocessPipeline subsystem (DESIGN.md §10).

(a) dual EL->CSR+CSC construction share of (build + PageRank-on-CSC);
    paper: 48-97% for the single build.
(b) per reorder-variant (reorder.REORDER_VARIANTS): pipeline cost
    (degrees + mapping + relabel + dual rebuild, per-stage timings from
    the PreprocessReport) against downstream kernels (pagerank /
    components / radii), plus the AMORTIZATION POINT — how many
    downstream PageRank iterations the reorder needs to pay for itself
    (paper: reordering is 25-55% of reorder+Radii). Radii rows surface
    the ``converged`` flag: a truncated BFS would otherwise silently
    underreport eccentricities (core/radii.py).

Run standalone with ``--smoke`` for the CI-sized pass; under
``benchmarks/run.py --smoke`` these rows land in BENCH_smoke.json (the
key-set the scripts/check_bench_rows.py regression guard protects).
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmarks.common import Rows, graph_scale, time_fn
from repro.core import (
    PreprocessPipeline,
    REORDER_VARIANTS,
    amortization_iters,
    build_csc,
    build_csr_csc,
    connected_components_fused,
    degrees_from_coo,
    graph_suite,
    pagerank_csr_pull,
)
from repro.core.radii import radii
from repro.core.reorder import relabel_coo

PR_ITERS = 10


def _pr_iter_seconds(csc, outdeg) -> float:
    """Per-iteration pull-PageRank seconds on one CSC layout."""
    return time_fn(
        lambda c, o: pagerank_csr_pull(c, o, iters=PR_ITERS).ranks, csc, outdeg
    ) / PR_ITERS


def run() -> Rows:
    rows = Rows()
    suite = graph_suite(graph_scale())
    for name, g in suite.items():
        outdeg = degrees_from_coo(g, by="src")

        # (a) dual-layout build share of build + downstream PageRank
        csr0, csc0 = build_csr_csc(g, method="auto")
        t_build = time_fn(lambda gg: build_csr_csc(gg, method="auto"), g)
        t_pr_orig_iter = _pr_iter_seconds(csc0, outdeg)
        t_pr = t_pr_orig_iter * PR_ITERS
        share = t_build / (t_build + t_pr)
        rows.add(
            f"fig2a/build_share/{name}",
            t_build * 1e6,
            f"build_share={share*100:.0f}% (paper: 48-97%)",
        )

        # (b) every reorder variant through the pipeline + amortization.
        # The pipeline warms each stage itself (an untimed first pass):
        # ``seconds`` is steady-state, the warmup pass is reported
        # separately (warmup_us) — amortization points are not compile-skewed.
        for variant in REORDER_VARIANTS:
            pipe = PreprocessPipeline(variant=variant, build_method="auto")
            res = pipe.run(g)
            rep = res.report
            stage_us = " ".join(
                f"{s.name}={s.seconds*1e6:.0f}us" for s in rep.stages
            )
            rows.add(
                f"fig2b/preproc/{variant}/{name}",
                rep.total_seconds * 1e6,
                f"{stage_us} warmup_us={rep.total_warmup_seconds*1e6:.0f} "
                f"modeled_bytes={rep.total_modeled_bytes:.3g} "
                f"decisions={len(rep.decisions())}",
            )

            # downstream kernels on the reordered layouts; the reordered
            # out-degrees are a permutation of the pipeline's histogram
            rel = relabel_coo(g, res.new_ids)
            reordered_outdeg = (
                jnp.zeros_like(res.degrees).at[res.new_ids].set(res.degrees)
            )
            t_pr_reord_iter = _pr_iter_seconds(res.csc, reordered_outdeg)
            t_cc = time_fn(
                lambda c: connected_components_fused(c, max_iters=64).labels,
                rel,
            )
            rad = radii(res.csr, k=4, max_iters=300)  # converged flag + warmup
            t_radii = time_fn(
                lambda c: radii(c, k=4, max_iters=300).ecc, res.csr, warmup=0
            )
            amort = amortization_iters(
                rep.total_seconds, t_pr_orig_iter, t_pr_reord_iter
            )
            amort_s = f"{amort:.1f}" if amort != float("inf") else "never"
            share_b = rep.total_seconds / (rep.total_seconds + t_radii)
            rows.add(
                f"fig2b/amortize/{variant}/{name}",
                rep.total_seconds * 1e6,
                f"amort_pr_iters={amort_s} "
                f"pr_iter_us(before/after)={t_pr_orig_iter*1e6:.0f}/"
                f"{t_pr_reord_iter*1e6:.0f} cc_us={t_cc*1e6:.0f} "
                f"radii_us={t_radii*1e6:.0f} "
                f"radii_converged={bool(rad.converged)} "
                f"reorder_share={share_b*100:.0f}% (paper: 25-55%)",
            )
    return rows


if __name__ == "__main__":
    import os
    import sys

    if "--smoke" in sys.argv[1:]:
        os.environ["BENCH_SCALE"] = "small"
        os.environ.setdefault("REPRO_BENCH_REPS", "1")
        os.environ.setdefault("REPRO_BENCH_WARMUP", "1")
    for r in run().emit():
        print(r)
