"""Profiler-trace reduction: device busy and idle time, per-op time and
the host activity behind each idle gap.

A run with ``--trace 1`` records its window with the JAX profiler
(``Session``), reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``
and keeps three kinds of event (``load``):

* device ops: the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane,
  each reduced to its name, opcode and whether it is a
  Pallas kernel (``parse_op``);
* windows: the benchmark's own host spans ``bench.job`` and
  ``bench.window``, which put the measured window on the trace's clock;
* host events: every other host span, to name the idle gaps.

:class:`View` reduces those events within the windows: busy time is the
union of the device op intervals, averaged over the chips; idle gaps are
the windows less that union.  The reduction is plain Python over event
tuples, so ``bench/tests/test_trace_reduce.py`` checks it on a small
recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Tuple

WINDOW_SPANS = ("bench.job", "bench.window")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# The TensorCore's own ops; "Async XLA Ops" (copies in flight) overlap
# them and are not counted as busy.
OPS_LINE = "XLA Ops"
# On a TPU an op's event name is its HLO instruction:
#   %bitonic_sort_kv.1 = (f32[8,1,1,65536]{...}, s32[...]) custom-call(...)
HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<shape>.*?) "
                 r"(?P<opcode>[a-z][\w\-]*)\(")

def parse_op(text: str) -> dict:
    """From an op's HLO text: ``name`` without its numeric suffix (a
    kernel's name), ``label`` (instruction and result shape, for the
    breakdown), ``opcode``, and whether it is a Pallas kernel."""
    m = HLO.match(text)
    if m is None:
        name = text.split(" ")[0].lstrip("%")
        return {"name": name, "label": name, "opcode": "", "pallas": False}
    shape = m.group("shape")
    return {"name": re.sub(r"\.\d+$", "", m.group("name")),
            "label": f"{m.group('name')} = {re.sub(r'{[^}]*}', '', shape)}",
            "opcode": m.group("opcode"),
            "pallas": 'custom_call_target="tpu_custom_call"' in text}

Interval = Tuple[float, float]


class Session:
    """A profiler trace of the window, with the Python tracer off (it
    would slow the host work that some cells measure)."""

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> "View":
        import jax
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            return View(**load(paths[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load(path: str) -> dict:
    """The events of one ``.xplane.pb`` that :class:`View` reads: device
    ops as ``[hlo_text, start_ns, end_ns]`` per chip, the window spans and
    the other host spans as ``[name, start_ns, end_ns]``."""
    from jax.profiler import ProfileData
    ops: Dict[int, list] = collections.defaultdict(list)
    windows, host = [], []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))].extend(
                    [e.name, e.start_ns, e.start_ns + e.duration_ns]
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    span = [e.name, e.start_ns, e.start_ns + e.duration_ns]
                    (windows if e.name in WINDOW_SPANS else host).append(span)
    return {"ops": dict(ops), "windows": windows, "host": host}


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def clip(intervals: List[Interval], windows: List[Interval]
         ) -> List[Interval]:
    """The parts of ``intervals`` inside the (disjoint, sorted)
    ``windows``."""
    out = []
    for a, b in intervals:
        for w0, w1 in windows:
            lo, hi = max(a, w0), min(b, w1)
            if hi > lo:
                out.append((lo, hi))
    return out


def length(intervals: List[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def gaps(busy: List[Interval], windows: List[Interval]) -> List[Interval]:
    """The windows less the (disjoint, sorted) busy intervals."""
    out = []
    for w0, w1 in windows:
        at = w0
        for a, b in busy:
            if b <= w0 or a >= w1:
                continue
            if a > at:
                out.append((at, a))
            at = max(at, b)
        if at < w1:
            out.append((at, w1))
    return out


class View:
    """The window of one traced run, reduced."""

    def __init__(self, ops: dict, windows: list, host: list):
        self.windows = union([(a, b) for _, a, b in windows])
        # each op parsed, with its in-window seconds
        self.ops = {int(d): [(a, b, parse_op(text),
                              length(clip([(a, b)], self.windows)) * 1e-9)
                             for text, a, b in evs]
                    for d, evs in ops.items()}
        self.host = sorted((tuple(e) for e in host), key=lambda e: e[1])
        self.window_s = length(self.windows) * 1e-9
        self._busy = {d: union(clip([(a, b) for a, b, _, _ in evs],
                                    self.windows))
                      for d, evs in self.ops.items()}
        self.busy_s = (sum(length(b) for b in self._busy.values())
                       / max(1, len(self._busy)) * 1e-9)

    def op_seconds(self, match=None) -> float:
        """Summed in-window time of the device ops that ``match(op)``
        accepts (all when None), averaged over the chips."""
        total = sum(s for evs in self.ops.values() for _, _, op, s in evs
                    if match is None or match(op))
        return total / max(1, len(self.ops))

    def op_events(self, match) -> list:
        """(op, in-window seconds) of each device op ``match`` accepts."""
        return [(op, s) for evs in self.ops.values() for _, _, op, s in evs
                if s > 0 and match(op)]

    def top_ops(self, k: int = 10) -> List[list]:
        """The ops that took most in-window time, by instruction and shape,
        averaged over the chips."""
        by_name = collections.Counter()
        for evs in self.ops.values():
            for _, _, op, s in evs:
                by_name[op["label"]] += s
        n = max(1, len(self.ops))
        return [[name, s / n] for name, s in by_name.most_common(k)]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """Idle time inside the windows, summed by what the host was doing:
        each gap goes to the host span that overlaps it most, the
        shortest such span on a tie (the innermost), or to ``no host
        span``."""
        by_what = collections.Counter()
        chips = self._busy or {0: []}
        starts = [a for _, a, _ in self.host]
        longest = max((b - a for _, a, b in self.host), default=0.0)
        for busy in chips.values():
            for g0, g1 in gaps(busy, self.windows):
                best, overlap = "no host span", 0.0
                lo = bisect.bisect_left(starts, g0 - longest)
                hi = bisect.bisect_right(starts, g1)
                shortest = None
                for name, a, b in self.host[lo:hi]:
                    o = min(b, g1) - max(a, g0)
                    if o > overlap or (o == overlap > 0 and b - a < shortest):
                        best, overlap, shortest = name, o, b - a
                by_what[best] += g1 - g0
        n = max(1, len(chips))
        return [[what, s / n * 1e-9] for what, s in by_what.most_common(k)]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}
