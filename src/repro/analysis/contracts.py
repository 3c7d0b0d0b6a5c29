"""Runtime contract checker for PB reduce streams (DESIGN.md §16.2).

The paper's correctness story is a contract between the partitioner and
the kernel: indices in bounds, bins covering the domain, the fused
accumulator resident in the fast level, caller order/bounds *claims*
actually true of the stream. "Making Caches Work for Graph Analytics"
(PAPERS.md, arXiv 1608.01362) frames cache-aware execution the same
way. This module makes the contract executable: ``check_stream`` runs
inside ``PBExecutor.reduce_stream`` / ``shard_reduce_stream`` on every
call.

Two levels:

  cheap  — always on. Pure host-side arithmetic on static shapes and
      the decision object: value-rank policy, stream-length agreement,
      bin-range legality, fused-accumulator legality, cache-key
      completeness. Zero device syncs; the cost is a few comparisons.
  full   — ``REPRO_PB_CHECK=1``. Additionally materializes the indices
      (skipped under a jax trace) and verifies the *data-dependent*
      claims: the in-bounds promise and the sortedness claim. CI runs
      one whole pytest leg at this level.

Violations raise :class:`ContractError` carrying the decision's
``describe()`` string, so the failure names what the executor chose,
not just what the caller passed.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional

import numpy as np

from repro.core import pb
from repro.core.plan import fused_fits, fused_vmem_bytes


class ContractError(ValueError):
    """A PB stream/decision contract violation.

    ``invariant`` is a stable machine-readable name for the violated
    clause (tests and tooling key on it); the message carries the
    decision's ``describe()`` so the report names the chosen execution.
    """

    def __init__(self, invariant: str, message: str):
        self.invariant = invariant
        super().__init__(f"[{invariant}] {message}")


def check_level() -> str:
    """The active check level: ``"full"`` when ``REPRO_PB_CHECK=1``,
    else ``"cheap"``. Read per call so tests can flip the env var."""
    return "full" if os.environ.get("REPRO_PB_CHECK", "0") == "1" else "cheap"


def _is_traced(x) -> bool:
    import jax

    return isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# Cache-key completeness (introspective).
# ---------------------------------------------------------------------------

# How each BinningDecision field is covered by the persisted autotune
# cache key. The contract: every field that affects what code runs must
# either appear in the key (directly or via the input that derives it)
# or be an output/provenance of the decision, and this registry is the
# reviewable statement of which is which. ``token``: a substring that
# must appear in the executor source as evidence the claimed axis is
# actually rendered.
_KEY_COVERAGE = {
    "method": {"how": "output"},  # the decision itself, not a key input
    "bin_range": {"how": "key", "token": ":r"},
    "num_bins": {"how": "derived"},  # num_indices / bin_range, both keyed
    "plan": {"how": "derived"},  # from (hw, num_indices, bin_range)
    "source": {"how": "provenance"},  # cache|autotuned|analytic|caller
    "pipeline_chunks": {"how": "key", "token": ":pipeline"},
    "f_tile": {"how": "key", "token": ":f"},  # via the feature_dim axis
}


@functools.lru_cache(maxsize=8)
def check_cache_key_completeness(decision_cls=None, executor_cls=None) -> None:
    """Fail loudly when a ``BinningDecision`` field has no declared
    cache-key coverage.

    The stale-decision bug class (PRs 3/8): a new axis lands on the
    decision (mesh topology, feature dim) but the persisted cache key
    doesn't carry it, so decisions measured under one configuration are
    silently replayed under another. This check introspects the
    dataclass fields against :data:`_KEY_COVERAGE` and verifies each
    claimed key axis is actually rendered by the executor source — a
    new field without a key axis fails here, at the first reduce of the
    test suite, not in a benchmark diff three PRs later.
    """
    import inspect

    if decision_cls is None or executor_cls is None:
        from repro.core.executor import BinningDecision, PBExecutor

        decision_cls = decision_cls or BinningDecision
        executor_cls = executor_cls or PBExecutor

    fields = {f.name for f in dataclasses.fields(decision_cls)}
    unknown = sorted(fields - set(_KEY_COVERAGE))
    if unknown:
        raise ContractError(
            "cache-key-completeness",
            f"decision field(s) {unknown} have no declared cache-key "
            "coverage: extend PBExecutor._key (and bump "
            "_CACHE_SCHEMA_VERSION) or register the field in "
            "repro.analysis.contracts._KEY_COVERAGE with how it is "
            "covered",
        )
    stale = sorted(set(_KEY_COVERAGE) - fields)
    if stale:
        raise ContractError(
            "cache-key-completeness",
            f"_KEY_COVERAGE claims field(s) {stale} that "
            f"{decision_cls.__name__} no longer carries — registry drift",
        )
    src = inspect.getsource(executor_cls)
    for name, cov in _KEY_COVERAGE.items():
        tok = cov.get("token")
        if tok and tok not in src:
            raise ContractError(
                "cache-key-completeness",
                f"decision field {name!r} claims cache-key token {tok!r} "
                f"but {executor_cls.__name__} source renders no such axis",
            )


# ---------------------------------------------------------------------------
# The stream contract.
# ---------------------------------------------------------------------------


def check_stream(
    indices,
    values,
    num_nodes: int,
    decision,
    *,
    op: str = "add",
    sorted_within: Optional[int] = None,
    in_bounds: bool = False,
    hw=None,
    level: Optional[str] = None,
) -> None:
    """Validate one (indices, values) reduce stream against ``decision``.

    Cheap clauses (always):
      value-rank   — ``pb.value_block_shape`` accepts the value array
                     and its stream length matches the index stream;
      bin-range    — ``bin_range >= 1``, ``num_bins >= 1`` and
                     ``num_bins * bin_range`` covers ``num_nodes`` (the
                     kernels assert the same; here it fails *before*
                     tracing, with the decision named);
      fused-fits   — an *analytic* fused decision's kernel footprint
                     (``plan.fused_fits``) fits the fast level at its
                     bin range and f_tile (measured/cached
                     fused decisions are evidence-backed and forced ones
                     carry the guarded jnp fallback, so only the model's
                     own claim is policed);
      cache-key-completeness — see :func:`check_cache_key_completeness`.

    Full clauses (``level="full"``, skipped for traced arrays):
      in-bounds    — ``in_bounds=True`` requires every index in
                     ``[0, num_nodes)``;
      sortedness   — ``sorted_within=r`` requires the bin ids at
                     granularity ``r`` to be non-decreasing
                     (``r <= 1``: the indices themselves).

    Raises :class:`ContractError` naming the violated invariant and the
    decision (``describe()``).
    """
    level = level or check_level()
    desc = decision.describe() if hasattr(decision, "describe") else str(decision)

    # -- cheap: structural/static clauses ---------------------------------
    vshape = pb.value_block_shape(values)  # raises its own typed errors
    m = int(indices.shape[0])
    if int(values.shape[0]) != m:
        raise ContractError(
            "stream-length",
            f"indices carry {m} tuples but values carry "
            f"{int(values.shape[0])} (decision {desc})",
        )
    if num_nodes < 0:
        raise ContractError(
            "domain", f"negative num_nodes={num_nodes} (decision {desc})"
        )
    if decision.bin_range < 1 or decision.num_bins < 1:
        raise ContractError(
            "bin-range",
            f"illegal binning geometry r={decision.bin_range}, "
            f"B={decision.num_bins} (decision {desc})",
        )
    if decision.num_bins * decision.bin_range < num_nodes:
        raise ContractError(
            "bin-range",
            f"bins do not cover the domain: {decision.num_bins} bins x "
            f"range {decision.bin_range} < num_nodes={num_nodes} "
            f"(decision {desc})",
        )
    if decision.f_tile and vshape and decision.f_tile > vshape[0]:
        raise ContractError(
            "f-tile",
            f"f_tile={decision.f_tile} wider than the value rows "
            f"F={vshape[0]} (decision {desc})",
        )
    if (
        decision.method == "fused"
        and decision.source == "analytic"
        and hw is not None
    ):
        itemsize = int(np.dtype(getattr(values, "dtype", np.float32)).itemsize)
        eff_cols = decision.f_tile or (vshape[0] if vshape else 0) or 1
        geometry = dict(
            bin_range=decision.bin_range, itemsize=itemsize, f_tile=eff_cols
        )
        if not fused_fits(hw, num_nodes, **geometry):
            raise ContractError(
                "fused-fits",
                f"analytic fused decision whose kernel footprint "
                f"({fused_vmem_bytes(num_nodes, **geometry)} B at {eff_cols} "
                f"resident column(s)) exceeds the fast level "
                f"({hw.fast_levels[-1]} B) — "
                f"fused_fits legality is broken (decision {desc})",
            )
    check_cache_key_completeness()

    if level != "full" or m == 0:
        return

    # -- full: data-dependent claims (device sync; REPRO_PB_CHECK=1) ------
    if _is_traced(indices):
        return  # claims on traced values are checked by the caller's tests
    idx = np.asarray(indices)
    if not np.issubdtype(idx.dtype, np.integer):
        raise ContractError(
            "index-dtype",
            f"stream indices must be integers, got {idx.dtype} "
            f"(decision {desc})",
        )
    if in_bounds:
        lo = int(idx.min())
        hi = int(idx.max())
        if lo < 0 or hi >= num_nodes:
            raise ContractError(
                "in-bounds",
                f"caller promised in_bounds but indices span "
                f"[{lo}, {hi}] outside [0, {num_nodes}) — the "
                f"promise_in_bounds scatter would corrupt memory on a "
                f"real backend (decision {desc})",
            )
    if sorted_within is not None and sorted_within >= 0:
        r = max(1, int(sorted_within))
        bids = idx // r
        if m > 1 and np.any(np.diff(bids) < 0):
            pos = int(np.argmax(np.diff(bids) < 0))
            claim = (
                "elementwise sorted" if r == 1 else f"bin-blocked at range {r}"
            )
            raise ContractError(
                "sortedness",
                f"caller claimed the stream is {claim}, but position "
                f"{pos} -> {pos + 1} goes {int(idx[pos])} -> "
                f"{int(idx[pos + 1])} backwards — a false "
                f"indices_are_sorted hint silently corrupts XLA "
                f"scatters (decision {desc})",
            )


__all__ = [
    "ContractError",
    "check_level",
    "check_stream",
    "check_cache_key_completeness",
]
