"""Pallas TPU kernels for PB/COBRA hot spots.

Importing this package does not import Pallas: the kernels live in the
submodules (``fused``, ``binning``, ``histogram``, ``binread``,
``scatter_rows``, ``ops``). What lives here is the one place that
decides whether a kernel call compiles for the chip or runs in Pallas
interpret mode.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas call runs in interpret mode.

    ``None`` resolves per backend: compiled Mosaic on a TPU, interpret
    mode everywhere else (the CPU test container). ``interpret=True`` on
    a TPU backend is refused, so no call on the chip runs the
    interpreter. ``False`` off TPU is allowed: it is how a kernel is
    lowered for a described (not attached) TPU.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend: kernels compile for the chip "
            "there (pass interpret=None)"
        )
    return bool(interpret)


def interpret_only(kernel: str, interpret: Optional[bool] = None) -> bool:
    """``resolve_interpret`` for a kernel with no Mosaic lowering: it
    runs in interpret mode off TPU and raises wherever it would have to
    compile (a TPU backend, or ``interpret=False``), never falling back
    to the interpreter on the chip."""
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            f"{kernel} has no TPU (Mosaic) lowering; it runs only in "
            "interpret mode off TPU"
        )
    return True


__all__ = ["interpret_only", "resolve_interpret"]
