"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Only a few things are read: the operations each device ran (the
``XLA Ops`` line of every ``/device:TPU:<n>`` plane), and the spans of
the benchmark's main thread on the host (``jax.profiler.TraceAnnotation``
names that start with ``bench.``, and, when the Python tracer was on,
the Python calls of that thread). Everything is clipped to the window,
the host span ``bench.traced`` that the benchmark opens around the
traced jobs.

  busy_s      — per device, the union of its operations' intervals in
                the window; averaged over the devices that ran any.
  op_seconds  — device self time of each operation name: its duration
                less that of the operations nested in it (a ``while``
                holds its body's), summed.
  gaps        — every interval of the window in which a device ran
                nothing, named by what the main thread was doing then:
                the innermost of its spans that holds the gap's midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    op_seconds: dict
    gaps: list  # (name, seconds), in time order

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, k: int = 10) -> list:
        """Device seconds by operation, largest first, under short names."""
        by_name: collections.Counter = collections.Counter()
        for name, s in self.op_seconds.items():
            by_name[short_op_name(name)] += s
        return [[n, s] for n, s in by_name.most_common(k)]

    def top_gaps(self, k: int = 10) -> list:
        """Idle seconds summed by what the host was doing, largest first."""
        by_name: collections.Counter = collections.Counter()
        for name, s in self.gaps:
            by_name[name] += s
        return [[n, s] for n, s in by_name.most_common(k)]

    def kernel_seconds(self, prefix: str) -> float:
        """Device seconds of every operation whose name starts with
        ``prefix``: the HLO instruction's own name, not an operand's."""
        return sum(s for n, s in self.op_seconds.items() if n.startswith(prefix))


def short_op_name(text: str) -> str:
    """``%fusion.7 fusion`` for the HLO text a TPU trace names its
    operations by (``%fusion.7 = s32[...]{...} fusion(...), ...``): the
    instruction and its opcode, with a custom call's target."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80]
    depth = 0
    for i, ch in enumerate(rest):  # skip the result shape, brackets and all
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            break
    else:
        return head
    opcode = rest[i + 1:].split("(", 1)[0]
    target = rest.partition('custom_call_target="')[2].split('"', 1)[0]
    return f"{head} {opcode}" + (f"[{target}]" if target else "")


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` that a ``jax.profiler`` session wrote."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals as disjoint, sorted intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def idle_intervals(busy: list, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi]`` that merged ``busy`` intervals leave."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def self_times(events) -> collections.Counter:
    """Self time of each name among ``(name, start, end)`` events of one
    line, where an event that starts inside another is nested in it."""
    out: collections.Counter = collections.Counter()
    stack: list = []  # [name, end, self time] of the open events
    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            name, _, own = stack.pop()
            out[name] += max(own, 0)
        if stack:
            stack[-1][2] -= e - s
        stack.append([n, e, e - s])
    for name, _, own in stack:
        out[name] += max(own, 0)
    return out


def name_gaps(gaps, spans) -> list:
    """For each gap, the innermost span of one thread that holds the gap's
    midpoint ("untraced" where none does). Spans of one thread nest, so a
    stack swept along the time axis holds exactly the open ones."""
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][0] + gaps[i][1])
    spans = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    names = ["untraced"] * len(gaps)
    stack, i = [], 0
    for g in order:
        t = (gaps[g][0] + gaps[g][1]) / 2
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] <= spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        if stack:
            names[g] = stack[-1][0]
    return names


def _events(line):
    return [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]


def reduce(path: str) -> Summary:
    """The benchmark's numbers from the trace file at ``path``."""
    from jax.profiler import ProfileData

    return summarize(ProfileData.from_file(path), path)


def summarize(pd, path: str = "trace") -> Summary:
    """The benchmark's numbers from a ``jax.profiler.ProfileData``."""
    device_ops, host_lines = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = [ev for line in plane.lines if line.name == OPS_LINE for ev in _events(line)]
            if ops:
                device_ops.append(ops)
        elif plane.name == HOST_PLANE:
            host_lines.extend(_events(line) for line in plane.lines)
    # the benchmark's main thread: the host line that holds its spans
    main = [evs for evs in host_lines if any(n.startswith(SPAN_PREFIX) for n, _, _ in evs)]
    if not main:
        raise ValueError(f"{path}: no host span named {SPAN_PREFIX}*")
    spans = main[0]
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: want one {WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0]
    if not device_ops:
        raise ValueError(f"{path}: no device operation in the trace")
    op_seconds: collections.Counter = collections.Counter()
    busy_total, gaps = 0.0, []
    for ops in device_ops:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]
        for n, ns in self_times(inside).items():
            op_seconds[n] += ns / 1e9
        busy = merge((s, e) for _, s, e in inside)
        busy_total += sum(e - s for s, e in busy)
        gaps.extend(idle_intervals(busy, lo, hi))
    gaps.sort()
    names = name_gaps(gaps, spans)
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / len(device_ops) / 1e9,
        devices=len(device_ops),
        op_seconds=dict(op_seconds),
        gaps=[(n, (e - s) / 1e9) for n, (s, e) in zip(names, gaps)],
    )
