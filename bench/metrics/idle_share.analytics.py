"""idle_share.analytics (%): the share of the traced window in which the
device ran no operation, 100 x (1 - busy / window), from the profiler
trace (``bench/trace.py``). Layer: device. Moves ``edges_per_s``.
"""


def read(ctx):
    s = ctx["trace"]
    return None if s is None else 100.0 * s.idle_share()
