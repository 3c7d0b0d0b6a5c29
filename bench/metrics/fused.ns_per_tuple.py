"""fused.ns_per_tuple (ns): summed device time of the Pallas fused
C-Buffer kernel's events in the traced window over the tuples it reduced
there (edges x iterations of the traced jobs, counted by the benchmark).
Layer: the fused kernel (``kernels/fused.py``). Moves ``edges_per_s``.

The kernel is matched by the name the trace gives its events on a v5e
today: the program's ``pallas_call`` carries no name, so its events are
HLO custom calls named after the enclosing function (``%closed_call.4``,
``%f.1``) whose target is ``tpu_custom_call``: ``KERNEL``. On the
PageRank path the fused kernel is the only Mosaic kernel. A cell whose
reduces take another path has no such events and reports nothing.
"""
KERNEL = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    s, c = ctx["trace"], ctx["counts"]
    if s is None or "tuples" not in c:
        return None
    seconds = s.kernel_seconds(KERNEL)
    if seconds <= 0:
        return None
    return seconds * 1e9 / (c["tuples"] * ctx["traced_jobs"])
