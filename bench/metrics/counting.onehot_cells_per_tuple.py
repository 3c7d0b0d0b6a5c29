"""counting.onehot_cells_per_tuple (cells): the one-hot cells that the
counting binning's block scan (``pb.counting_permutation``) builds per
tuple of a PageRank reduce, ``num_bins`` x the stream padded to whole
blocks / the stream: the work its cumsum does, as a count. Read from the
attrs of the window's ``pagerank.run`` roots (method, ``num_bins``,
``block``, ``num_edges``). Layer: counting binning. Moves
``edges_per_s``.

None where a window job's decision is not ``counting`` (the fused kernel
bins nothing) or its span lacks the attrs, as in a program that predates
them.
"""
from bench import program_spans

JOB = "pagerank.run"


def read(ctx):
    jobs = len(ctx["readings"])
    found = program_spans.recorded() if jobs else None
    if found is None:
        return None
    roots = [s for s in found if s.name == JOB and s.parent_id is None][-jobs:]
    if len(roots) < jobs:
        return None
    cells = []
    for r in roots:
        a = r.attrs
        if a.get("method") != "counting" or not {"num_bins", "block", "num_edges"} <= set(a):
            return None
        m, block = int(a["num_edges"]), int(a["block"])
        cells.append(int(a["num_bins"]) * -(-m // block) * block / m)
    return sum(cells) / len(cells)
