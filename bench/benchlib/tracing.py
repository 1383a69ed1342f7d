"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The window is the host span ``bench.window`` that the harness opens around
the measured calls.  Device operations are the events of the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane, named there by their HLO text
(``%cwfl_round.8 = (f32[50,184320]...) custom-call(...)``); an op is known
by its instruction name (``cwfl_round.8``).  A trace with no such plane
is an error, except in a CPU rehearsal (``host_ops=True``), where the XLA
op events of the host's ``tf_XLA*`` threads stand in.
Every interval is clipped to the window.  Busy time is the length of the
union of the operations' intervals; an operation's self time is its
duration less that of the operations nested inside it on the same line.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."


DESCRIPTION_CHARS = 160


def op_name(text: str) -> str:
    """``%fusion.717 = bf16[3200,28,28]{...} fusion(...)`` → ``fusion.717``."""
    return text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]                    # ns, trace clock
    devices: dict[str, list[tuple[str, float, float]]]
    host_spans: list[tuple[str, float, float]]
    descriptions: dict[str, str] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, device: str) -> float:
        return union_ns([(s, e) for _, s, e in self.devices[device]]) * 1e-9

    def mean_busy_s(self) -> float:
        """Busy seconds averaged over the traced devices."""
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def op_seconds(self, match) -> float:
        """Summed device durations of the ops whose name ``match`` accepts,
        over every device."""
        return sum(e - s for evs in self.devices.values()
                   for n, s, e in evs if match(n)) * 1e-9

    def op_count(self, match) -> int:
        return sum(1 for evs in self.devices.values() for n, _, _ in evs
                   if match(n))

    def top_ops(self, n: int = 10) -> list[list]:
        """[op, self seconds] of the ``n`` ops with the most self time,
        summed over devices; the op as the start of its HLO text."""
        total: dict[str, float] = {}
        for evs in self.devices.values():
            for name, secs in self_times(evs):
                total[name] = total.get(name, 0.0) + secs
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[self.descriptions.get(k, k), v] for k, v in top]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """[host activity, seconds] of the ``n`` longest intervals of the
        window in which no op ran on the first device; the activity is the
        innermost ``bench.*`` host span around the gap's midpoint."""
        dev = sorted(self.devices)[0]
        gaps = complement(merge([(s, e) for _, s, e in self.devices[dev]]),
                          self.window)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = 0.5 * (s + e)
            around = [(hs, he, name) for name, hs, he in self.host_spans
                      if hs <= mid <= he and name != WINDOW_SPAN]
            label = (min(around, key=lambda t: t[1] - t[0])[2]
                     if around else "host:other")
            out.append([label, (e - s) * 1e-9])
        return out


def merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def complement(merged, window):
    lo, hi = window
    gaps, cur = [], lo
    for s, e in merged:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def self_times(events):
    """(name, self seconds) per event: its duration less the durations of
    the events directly nested in it."""
    out, stack = [], []           # stack of [name, start, end, child_ns]
    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and s >= stack[-1][2]:
            n, ss, ee, child = stack.pop()
            out.append((n, (ee - ss - child) * 1e-9))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    for n, ss, ee, child in stack:
        out.append((n, (ee - ss - child) * 1e-9))
    return out


def _clip(s, e, window):
    return max(s, window[0]), min(e, window[1])


def reduce(profile, host_ops: bool = False) -> Trace:
    """A :class:`Trace` of a ``jax.profiler.ProfileData``; ``host_ops``
    lets the host's XLA threads stand in where no device plane exists."""
    host_spans = []
    host_xla = []
    devices: dict[str, list] = {}
    descriptions: dict[str, str] = {}
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                evs = devices[plane.name] = []
                for ev in line.events:
                    text = ev.name
                    name = op_name(text)
                    if name not in descriptions:
                        descriptions[name] = text[:DESCRIPTION_CHARS]
                    evs.append((name, ev.start_ns, ev.end_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host_spans.append((ev.name, ev.start_ns, ev.end_ns))
                    elif (line.name.startswith("tf_XLA")
                          and ev.duration_ns > 0
                          and not ev.name.startswith("Threadpool")):
                        host_xla.append((ev.name, ev.start_ns, ev.end_ns))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {WINDOW_SPAN!r} spans,"
                         f" expected 1")
    window = windows[0]
    if not devices:
        if not host_ops:
            raise ValueError("trace holds no /device:TPU plane with an "
                             "'XLA Ops' line")
        devices = {"/host:CPU": host_xla}
    clipped = {}
    for dev, evs in devices.items():
        keep = []
        for name, s, e in evs:
            s, e = _clip(s, e, window)
            if e > s:
                keep.append((name, s, e))
        clipped[dev] = keep
    return Trace(window=window, devices=clipped, host_spans=host_spans,
                 descriptions=descriptions)


def load(trace_dir: str, host_ops: bool = False) -> Trace:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{trace_dir}: {len(paths)} .xplane.pb files, "
                         f"expected 1")
    return reduce(ProfileData.from_file(paths[0]), host_ops)
