"""pagerank.roofline_share (%): the compulsory HBM bytes of the PageRank
iterations in the traced window (``bench/roofline.py``) over what the
chip's peak bandwidth (``bench/peaks.py``) moves in the device-busy
seconds of that window. Layer: the PageRank step. Moves ``edges_per_s``.
"""
from bench import peaks, roofline


def read(ctx):
    s, c = ctx["trace"], ctx["counts"]
    if s is None or "iterations" not in c or s.busy_s <= 0:
        return None
    moved = roofline.pagerank_bytes(c["num_nodes"], c["num_edges"], c["iterations"] * ctx["traced_jobs"])
    return roofline.roofline_share(moved, s.busy_s, peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"])
