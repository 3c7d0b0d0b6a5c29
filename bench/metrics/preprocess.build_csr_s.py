"""preprocess.build_csr_s (s): the program's own synchronized time of the
``build_csr`` stage (``StageReport.seconds``), averaged over the window's
jobs. Layer: preprocess. Moves ``build_edges_per_s``.
"""
STAGE = "stage.build_csr"


def read(ctx):
    times = [r[STAGE] for r in ctx["readings"] if STAGE in r]
    return sum(times) / len(times) if times else None
