"""idle_share.build (%): the share of the traced window in which the
device ran no operation, 100 x (1 - busy / window), from the profiler
trace (``bench/trace.py``). Layer: device. Moves ``build_edges_per_s``.
"""


def read(ctx):
    s = ctx["trace"]
    return None if s is None else 100.0 * s.idle_share()
