"""PreprocessPipeline — end-to-end PB-accelerated preprocessing (DESIGN.md §10).

The paper's headline claim is that pre-processing (EL->CSR construction,
reordering) is itself a PB workload that can cost as much as the
downstream kernel. This module composes the repo's preprocessing stages
into ONE subsystem so that claim is measurable end-to-end:

  degrees   — fused degree counting (commutative add through
              ``PBExecutor.reduce_stream``; sharded over a mesh when one
              is given);
  mapping   — a reorder variant from ``reorder.REORDER_VARIANTS``
              (degree_sort / hub_sort / dbg / random / identity) applied
              to the stage-1 histogram — the degree pass is shared, not
              recomputed;
  relabel   — endpoint rewrite under the new ids;
  build_csr — Neighbor-Populate of the relabeled Edgelist (any
              ``neighbor_populate.build_csr`` method, ``sharded`` through
              ``distributed_pb.shard_build_csr`` when a mesh is given);
  build_csc — the dual pull layout from the dst-keyed stream of the SAME
              relabeled Edgelist (``build_csr_csc``'s per-direction
              stream sharing), so pull kernels (``pagerank_csr_pull``)
              get their input from the same pipeline.

Every PB stage routes through ``PBExecutor.decide``/``reduce_stream`` —
no stage hardcodes a method, so fused-accumulator legality (DESIGN.md
§8.1) and topology-keyed autotune decisions apply to preprocessing
exactly as they do to processing. The pipeline returns a
``PreprocessReport``: per-stage wall-clock, modeled sequential bytes
(``traffic.preproc_stage_bytes``), and the executor decisions each stage
took — what ``benchmarks/fig2_preproc_cost.py`` turns into the paper's
Fig. 2 story plus the amortization point.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import neighbor_populate as npop
from repro.core import traffic
from repro.core.executor import PBExecutor, get_default_executor
from repro.core.graph import COO, CSR, SlackCSR
from repro.core.reorder import REORDER_VARIANTS, relabel_coo, reorder_mapping
from repro.core.spans import span


@dataclass(frozen=True)
class StageReport:
    """One pipeline stage: what ran, how long, what it should have moved."""

    name: str
    seconds: float  # the ``preprocess.<name>`` span's duration
    modeled_bytes: float
    # the PBExecutor decision-log entries this stage appended (method,
    # bin_range, source per decided stream) — empty for pure-relabel
    # stages and for caller-forced methods
    decisions: Tuple[dict, ...] = ()
    # wall-clock of the warmup pass (trace + compile + first run), the
    # ``preprocess.<name>.warmup`` span; ``seconds`` is the steady-state
    # pass that follows. 0.0 when the pipeline ran cold (warmup=False) —
    # then ``seconds`` includes compilation and must not feed
    # amortization math.
    warmup_seconds: float = 0.0

    def describe(self) -> str:
        ms = ", ".join(
            f"{d['method']}@r{d['bin_range']}[{d['source']}]" for d in self.decisions
        )
        return f"{self.name}: {self.seconds*1e6:.0f}us {self.modeled_bytes:.3g}B" + (
            f" ({ms})" if ms else ""
        )


@dataclass(frozen=True)
class PreprocessReport:
    """Per-stage account of one pipeline run (DESIGN.md §10.3)."""

    variant: str
    build_method: str
    num_nodes: int
    num_edges: int
    sharded: bool
    stages: Tuple[StageReport, ...]

    @property
    def total_seconds(self) -> float:
        return sum(s.seconds for s in self.stages)

    @property
    def total_warmup_seconds(self) -> float:
        return sum(s.warmup_seconds for s in self.stages)

    @property
    def total_modeled_bytes(self) -> float:
        return sum(s.modeled_bytes for s in self.stages)

    def stage(self, name: str) -> StageReport:
        for s in self.stages:
            if s.name == name:
                return s
        raise KeyError(f"no stage {name!r} in {[s.name for s in self.stages]}")

    def decisions(self) -> Tuple[dict, ...]:
        return tuple(d for s in self.stages for d in s.decisions)

    def as_dict(self) -> dict:
        return {
            "variant": self.variant,
            "build_method": self.build_method,
            "num_nodes": self.num_nodes,
            "num_edges": self.num_edges,
            "sharded": self.sharded,
            "total_seconds": self.total_seconds,
            "total_modeled_bytes": self.total_modeled_bytes,
            "stages": [
                {
                    "name": s.name,
                    "seconds": s.seconds,
                    "warmup_seconds": s.warmup_seconds,
                    "modeled_bytes": s.modeled_bytes,
                    "decisions": list(s.decisions),
                }
                for s in self.stages
            ],
        }


class PreprocessResult(NamedTuple):
    """What the pipeline hands downstream: both layouts + the mapping."""

    csr: CSR
    csc: Optional[CSR]
    new_ids: jnp.ndarray
    degrees: jnp.ndarray  # in-pipeline degree histogram (pre-relabel ids)
    report: PreprocessReport
    # the mutable layout (DESIGN.md §15), built as a timed pipeline stage
    # when ``slack_headroom`` was set — None otherwise
    slack: Optional[SlackCSR] = None


def amortization_iters(
    preproc_seconds: float, iter_seconds_before: float, iter_seconds_after: float
) -> float:
    """Downstream iterations needed to pay for preprocessing — the
    amortization point of the paper's Fig. 2b trade: reorder cost divided
    by the per-iteration saving it buys. ``inf`` when the reordered
    layout is no faster (the reorder never pays)."""
    gain = iter_seconds_before - iter_seconds_after
    if gain <= 0.0:
        return float("inf")
    return preproc_seconds / gain


class PreprocessPipeline:
    """Composable EL -> (reordered CSR [+ CSC]) pipeline.

    Parameters
    ----------
    variant:      a ``reorder.REORDER_VARIANTS`` key (``identity`` makes
                  the pipeline a pure dual-layout build — the
                  amortization baseline).
    build_method: ``neighbor_populate.BUILD_METHODS`` entry for the
                  rebuild stage; ``auto`` (default) lets the executor
                  decide, ``sharded`` is implied by passing ``mesh``.
    with_csc:     also build the pull layout (default True).
    mesh:         a 1-D device mesh: degree counting and both builds run
                  through the sharded paths (DESIGN.md §9).
    executor:     the PBExecutor to route through (process default when
                  None) — its decision log feeds the report.
    warmup:       run each stage once untimed before the timed pass
                  (default True): ``StageReport.seconds`` is then
                  steady-state and the warmup's wall-clock lands in
                  ``StageReport.warmup_seconds``. False times stages
                  cold — only for measuring compile cost itself.
    slack_headroom: when set, a final "slack" stage re-slacks the built
                  CSR into the mutable ``SlackCSR`` layout (DESIGN.md
                  §15) with this per-vertex headroom fraction;
                  ``PreprocessResult.slack`` carries it. The update
                  rebuild path (``updates.rebuild_slack_csr``) rides
                  this, so rebuild cost is stage-attributed like every
                  other preprocessing cost.
    """

    def __init__(
        self,
        variant: str = "degree_sort",
        build_method: str = "auto",
        *,
        with_csc: bool = True,
        bin_range: Optional[int] = None,
        mesh=None,
        axis_name: Optional[str] = None,
        executor: Optional[PBExecutor] = None,
        seed: int = 0,
        warmup: bool = True,
        slack_headroom: Optional[float] = None,
        slack_min_slack: int = 4,
    ):
        if variant not in REORDER_VARIANTS:
            raise ValueError(
                f"unknown reorder variant: {variant!r} (want one of "
                f"{tuple(REORDER_VARIANTS)})"
            )
        if build_method not in npop.BUILD_METHODS:
            raise ValueError(
                f"unknown build method: {build_method!r} "
                f"(want one of {npop.BUILD_METHODS})"
            )
        self.variant = variant
        self.build_method = "sharded" if mesh is not None else build_method
        self.with_csc = with_csc
        self.bin_range = bin_range
        self.mesh = mesh
        self.axis_name = axis_name
        if slack_headroom is not None and slack_headroom < 0:
            raise ValueError(
                f"slack_headroom must be >= 0, got {slack_headroom}"
            )
        self.executor = executor
        self.seed = seed
        self.warmup = warmup
        self.slack_headroom = slack_headroom
        self.slack_min_slack = slack_min_slack

    # -- stage driver ------------------------------------------------------

    def _run_stage(self, stages, ex, name, modeled_bytes, fn):
        """Time one stage (synchronized) as the span
        ``preprocess.<name>``, capturing the executor decisions it takes
        via an uncapped sink — the shared ``decision_log`` saturates at
        its cap, this channel never drops a stage's entries.

        Stages used to be timed cold, so first-run numbers included JIT
        trace/compile and skewed the fig2 amortization points. With
        ``warmup`` (the default) an untimed first pass, the span
        ``preprocess.<name>.warmup``, absorbs compilation — reported
        separately as ``warmup_seconds`` — and ``seconds`` is the
        steady-state pass the amortization math wants. The sink is
        attached only around the timed pass so decisions aren't
        double-counted (``decide`` runs on every invocation)."""
        warmup_s = 0.0
        if self.warmup:
            with span(f"preprocess.{name}.warmup") as sp:
                jax.block_until_ready(fn())
            warmup_s = sp.seconds
        sink: list = []
        ex.add_decision_sink(sink)
        try:
            with span(f"preprocess.{name}", stage=name) as sp:
                out = fn()
                jax.block_until_ready(out)
                sp.set(decisions=";".join(
                    f"{d['method']}@r{d['bin_range']}" for d in sink
                ))
        finally:
            ex.remove_decision_sink(sink)
        stages.append(
            StageReport(
                name=name,
                seconds=sp.seconds,
                modeled_bytes=modeled_bytes,
                decisions=tuple(sink),
                warmup_seconds=warmup_s,
            )
        )
        return out

    def run(self, coo: COO) -> PreprocessResult:
        """Every stage once, under the root span ``preprocess.run``."""
        with span(
            "preprocess.run", over=(coo.src,), variant=self.variant,
            build_method=self.build_method, num_nodes=coo.num_nodes,
            num_edges=coo.num_edges,
        ):
            return self._run(coo)

    def _run(self, coo: COO) -> PreprocessResult:
        ex = self.executor or get_default_executor()
        n, m = coo.num_nodes, coo.num_edges
        stages: list = []
        bm = "baseline" if self.build_method == "baseline" else "pb"

        def stage_bytes(stage):
            return traffic.preproc_stage_bytes(stage, m, n, build_method=bm)

        # 1. degrees — ONE fused-eligible reduction shared by the mapping
        # stage (the executor decides the method; sharded over the mesh)
        ones = jnp.ones((m,), jnp.int32)
        if self.mesh is not None:
            degrees = self._run_stage(
                stages, ex, "degrees", stage_bytes("degrees"),
                lambda: ex.shard_reduce_stream(
                    coo.src, ones, out_size=n, mesh=self.mesh, op="add",
                    axis_name=self.axis_name,
                ),
            )
        else:
            degrees = self._run_stage(
                stages, ex, "degrees", stage_bytes("degrees"),
                lambda: ex.reduce_stream(coo.src, ones, out_size=n, op="add"),
            )

        # 2. mapping — the registered variant over the shared histogram
        new_ids = self._run_stage(
            stages, ex, "mapping", stage_bytes("mapping"),
            lambda: reorder_mapping(
                self.variant, coo.src, n, seed=self.seed, degrees=degrees
            ),
        )

        # 3. relabel — endpoint rewrite (no PB stream: pure gathers)
        relabeled = self._run_stage(
            stages, ex, "relabel", stage_bytes("relabel"),
            lambda: relabel_coo(coo, new_ids),
        )

        # 4/5. dual rebuild — one binned stream per direction. The CSR
        # build reuses stage 1's histogram (permuted under the new ids:
        # one n-sized scatter instead of a second m-edge reduction); the
        # CSC direction needs the dst histogram and computes its own.
        build_kw = dict(
            method=self.build_method, bin_range=self.bin_range,
            mesh=self.mesh, axis_name=self.axis_name,
        )
        deg_relabeled = jnp.zeros_like(degrees).at[new_ids].set(degrees)
        csr = self._run_stage(
            stages, ex, "build_csr", stage_bytes("build_csr"),
            lambda: npop.build_csr(relabeled, degrees=deg_relabeled, **build_kw),
        )
        csc = None
        if self.with_csc:
            csc = self._run_stage(
                stages, ex, "build_csc", stage_bytes("build_csc"),
                lambda: npop.build_csc(relabeled, **build_kw),
            )

        # 6. slack — the mutable re-slack of the built CSR (§15), only
        # when asked: immutable consumers never pay the slab copy
        slack = None
        if self.slack_headroom is not None:
            slack = self._run_stage(
                stages, ex, "slack", stage_bytes("slack"),
                lambda: SlackCSR.from_csr(
                    csr,
                    headroom=self.slack_headroom,
                    min_slack=self.slack_min_slack,
                ),
            )

        report = PreprocessReport(
            variant=self.variant,
            build_method=self.build_method,
            num_nodes=n,
            num_edges=m,
            sharded=self.mesh is not None,
            stages=tuple(stages),
        )
        return PreprocessResult(
            csr=csr, csc=csc, new_ids=new_ids, degrees=degrees, report=report,
            slack=slack,
        )
