"""preprocess.slack_layout_s (s): host seconds of the slack stage's slab
layout in numpy (the program's ``slack_csr.layout`` spans, from after the
CSR came back to the hand-off of the slabs to the device), summed per
job and averaged over the window's jobs. Layer: preprocess. Moves
``build_edges_per_s``.
"""
from bench import program_spans

SPAN = "slack_csr.layout"


def read(ctx):
    return program_spans.seconds_per_job(ctx, SPAN)
