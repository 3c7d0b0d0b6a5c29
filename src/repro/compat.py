"""Single import sites for jax APIs this repo pins a spelling of.

The repo is written for the installed jax (0.9). Modules import
``shard_map`` and ``segment_sum`` from here, so a future move of either
touches one file; ``cost_analysis`` gives callers a dict even where the
compiled program reports no costs.
"""
from __future__ import annotations

from jax import shard_map
from jax.ops import segment_sum


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a (possibly empty) dict."""
    return compiled.cost_analysis() or {}


__all__ = ["shard_map", "cost_analysis", "segment_sum"]
