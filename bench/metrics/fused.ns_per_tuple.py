"""fused.ns_per_tuple (ns): summed device time of the Pallas fused
C-Buffer kernel's events in the traced window over the tuples it reduced
there (edges x iterations of the traced jobs, counted by the benchmark).
Layer: the fused kernel (``kernels/fused.py``). Moves ``edges_per_s``.

The kernel is matched by its own name: its ``pallas_call`` is named
``pb_fused_reduce``, so its events are HLO custom calls whose name
starts with ``KERNEL`` (``%pb_fused_reduce.3``). The exchange's, the
binning's and the row kernel's Mosaic calls (``%pb_fused_reduce_rows.1``
among them) have names of their own and are not counted. A cell whose
reduces take another path has no such events and reports nothing.
"""
KERNEL = "%pb_fused_reduce."


def read(ctx):
    s, c = ctx["trace"], ctx["counts"]
    if s is None or "tuples" not in c:
        return None
    seconds = s.kernel_seconds(KERNEL)
    if seconds <= 0:
        return None
    return seconds * 1e9 / (c["tuples"] * ctx["traced_jobs"])
