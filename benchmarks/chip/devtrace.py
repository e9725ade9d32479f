"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

``jax.profiler.ProfileData`` gives planes, their lines and their events,
each with a start and a duration in nanoseconds on one clock.  A device is
a plane named ``/device:<KIND>:<n>``; the operations it ran are the events
of its ``XLA Ops`` line.  The host's spans are events of the
``/host:CPU`` plane; the benchmark's own, named ``query.<step>`` by the
generators (``query.put``, ``query.call``, ``query.wait``,
``query.fetch``), bound the window and name what the host was doing while
the device sat idle.

Device busy time is the union of a device's operation intervals inside
the window, averaged over the devices that ran any.  On a TPU an
operation's event is named by its HLO instruction
(``%fusion.11 = f32[128,112,112,64]{...} fusion(...)``), so the name
gives its output shape; an operation is attributed to a group of layers
when two adjacent dimensions of that shape are a height and width the
group produces.
"""

from __future__ import annotations

import bisect
import collections
import re

SPAN_PREFIX = "query."
OPS_LINE = "XLA Ops"
NO_SPAN = "(no span)"
# "%name = f32[128,56,56,64]{...} fusion(" or "%name = (f32[..], ...) copy-start("
_INSTR = re.compile(r"^\s*%?([\w.\-]+)\s*=\s*\(?\s*\w+\[([\d,]*)\]")


def parse_op(event_name: str) -> tuple[str, tuple[int, ...]]:
    """(instruction name, dimensions of its first output) of an operation
    event; the dimensions are empty where the name holds no shape."""
    m = _INSTR.match(event_name)
    if not m:
        return event_name, ()
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def has_rows(dims: tuple[int, ...], rows: set[int]) -> bool:
    """True if ``dims`` hold a height x width map of one of ``rows``."""
    return any(a == b and a in rows for a, b in zip(dims, dims[1:]))


def merged(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """(start, end) intervals merged where they overlap, in order."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Reduced:
    """A traced window: device operations, host spans and the busy union."""

    def __init__(self, ops: dict[str, list[tuple[str, float, float]]],
                 spans: list[tuple[str, float, float]]) -> None:
        self.ops = ops                  # device -> [(event name, start, end)] ns
        self.spans = spans              # [(name, start, end)] ns
        self.t0 = min(s for _, s, _ in spans)
        self.t1 = max(e for _, _, e in spans)
        self.window_s = (self.t1 - self.t0) / 1e9
        busy = [sum(e - s for s, e in merged(self._clipped(evs)))
                for evs in ops.values()]
        self.busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0

    def _clipped(self, evs) -> list[tuple[float, float]]:
        return [(max(s, self.t0), min(e, self.t1)) for _, s, e in evs
                if e > self.t0 and s < self.t1]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, rows: set[int] | None = None) -> float:
        """Device seconds of the operations (of a group, given its output
        rows), averaged over devices."""
        total = 0.0
        for evs in self.ops.values():
            for name, s, e in evs:
                if rows is None or has_rows(parse_op(name)[1], rows):
                    total += e - s
        return total / max(1, len(self.ops)) / 1e9

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations that took most device time, by name."""
        per: collections.Counter = collections.Counter()
        for evs in self.ops.values():
            for name, s, e in evs:
                op, dims = parse_op(name)
                label = f"{op} {list(dims)}" if dims else op
                per[label] += (e - s) / 1e9 / len(self.ops)
        return [[label, sec] for label, sec in per.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle device time in the window, summed by the host span that
        overlaps each gap most; the ``n`` largest."""
        per: collections.Counter = collections.Counter()
        spans = sorted(self.spans, key=lambda t: t[1])
        starts = [s for _, s, _ in spans]
        for evs in self.ops.values():
            busy = merged(self._clipped(evs))
            edges = [self.t0] + [t for b in busy for t in b] + [self.t1]
            for g0, g1 in zip(edges[::2], edges[1::2]):
                if g1 > g0:
                    per[_label(spans, starts, g0, g1)] += \
                        (g1 - g0) / 1e9 / len(self.ops)
        return [[name, sec] for name, sec in per.most_common(n)]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _label(spans, starts, g0: float, g1: float) -> str:
    best, best_overlap = NO_SPAN, 0.0
    i = bisect.bisect_right(starts, g1)
    for name, s, e in reversed(spans[max(0, i - 64):i]):
        ov = min(e, g1) - max(s, g0)
        if ov > best_overlap:
            best, best_overlap = name, ov
    return best


def reduce(path: str) -> Reduced:
    """Read an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(pd) -> Reduced:
    ops: dict[str, list[tuple[str, float, float]]] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if evs:
                ops[plane.name] = evs
        elif plane.name == "/host:CPU":
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return Reduced(ops, spans)
