"""preprocess.degrees_s (s): the program's own synchronized time of the
``degrees`` stage (the fused add reduce of the out-degrees,
``StageReport.seconds``), averaged over the window's jobs. Layer:
preprocess. Moves ``build_edges_per_s``.
"""
STAGE = "stage.degrees"


def read(ctx):
    times = [r[STAGE] for r in ctx["readings"] if STAGE in r]
    return sum(times) / len(times) if times else None
