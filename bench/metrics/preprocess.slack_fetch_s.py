"""preprocess.slack_fetch_s (s): seconds the slack stage waits for the
built CSR's offsets to come back from the device (the program's
``slack_csr.fetch`` spans; the neighbours stay there), summed per job
and averaged over the window's jobs. Layer: preprocess. Moves
``build_edges_per_s``.
"""
from bench import program_spans

SPAN = "slack_csr.fetch"


def read(ctx):
    return program_spans.seconds_per_job(ctx, SPAN)
