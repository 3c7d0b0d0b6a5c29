"""preprocess.slack_layout_s (s): seconds of the slack stage's slab
layout (the program's ``slack_csr.layout`` spans: from after the CSR's
offsets came back, the host's capacity math, the upload of the slack
table and the two device programs that place the arcs in their slabs,
synced inside the span), summed per job and averaged over the window's
jobs. Layer: preprocess. Moves ``build_edges_per_s``.
"""
from bench import program_spans

SPAN = "slack_csr.layout"


def read(ctx):
    return program_spans.seconds_per_job(ctx, SPAN)
