"""Reduction from a profiler trace to the numbers the per-layer metrics
read. Kept with the benchmark, so that every PR computes them alike.

A trace is reduced to plain intervals on one clock (nanoseconds):

* per device, the operations of its ``XLA Ops`` line, each named by its
  HLO instruction (``fusion.12``, ``flash_attention.3``: the event's name
  is the instruction's whole text), and the programs of its
  ``XLA Modules`` line, named by the jitted function
  (``jit__decode_fn``: the event's name adds a fingerprint). A loop's
  operation holds the operations of its body, so the ``leaves`` of a
  device are those that hold no other;
* on the host, the benchmark's own spans (``TraceAnnotation`` names that
  start with ``bench.``), the window span among them.

Everything below works on those intervals alone, so the tests build them
by hand.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = ["Trace", "load", "merge", "measure", "busy_ns", "ops_named",
           "exposed_collective_ns", "idle_gaps", "top_ops", "COLLECTIVES"]

WINDOW_SPAN = "bench.window"
# XLA's collective operations, with their async -start / -done halves
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_SUFFIX = re.compile(r"(\.\d+)+$")
_INSTRUCTION = re.compile(r"%?([^\s=(]+)")


@dataclasses.dataclass
class Trace:
    ops: dict  # device id -> [(name, start, end)] of its XLA Ops line
    modules: dict  # device id -> [(name, start, end)] of its XLA Modules line
    host: list  # [(name, start, end)] of the benchmark's host spans
    window: tuple  # (start, end) of the traced window

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def leaves(self, dev) -> list:
        """The operations of ``dev`` that hold no other operation."""
        evs = sorted(self.ops.get(dev, []), key=lambda x: (x[1], -x[2]))
        return [a for a, b in zip(evs, evs[1:] + [None]) if b is None or b[1] >= a[2]]


def instruction(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``;
    ``jit__decode_fn(1254...)`` -> ``jit__decode_fn``."""
    m = _INSTRUCTION.match(name)
    return m.group(1) if m else name


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if m:
                dev = int(m.group(1))
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = [(instruction(e.name), int(e.start_ns), int(e.end_ns))
                           for e in line.events]
                    (ops if line.name == "XLA Ops" else modules)[dev] = evs
            elif plane.name.startswith("/host:"):
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events if e.name.startswith("bench."))
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return Trace(ops=ops, modules=modules, host=host, window=spans[-1])


def merge(intervals, lo=None, hi=None) -> list:
    """Sorted, disjoint (start, end) pairs covering ``intervals``
    (``(name, start, end)`` or ``(start, end)``), clipped to [lo, hi]."""
    pairs = []
    for it in intervals:
        s, e = it[-2], it[-1]
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e > s:
            pairs.append((s, e))
    pairs.sort()
    out = []
    for s, e in pairs:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(merged) -> int:
    return sum(e - s for s, e in merged)


def _intersect(a, b) -> int:
    """Length of the overlap of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_ns(trace: Trace, dev) -> int:
    """Nanoseconds of the window in which some operation ran on ``dev``."""
    return measure(merge(trace.ops.get(dev, []), *trace.window))


def _is(name: str, base: str) -> bool:
    return name == base or name.startswith(base + ".") or name.startswith(base + "-")


def ops_named(trace: Trace, base: str, lines: str = "ops") -> list:
    """Every event of the window, on any device, whose name is ``base``
    or ``base`` with a numeric suffix (``flash_attention.3``); from the
    operations, or with ``lines='modules'`` the programs."""
    lo, hi = trace.window
    src = trace.ops if lines == "ops" else trace.modules
    return [(n, s, e) for evs in src.values() for n, s, e in evs
            if _is(n, base) and s >= lo and e <= hi]


def _collective(name: str) -> bool:
    return any(_is(name, c) for c in COLLECTIVES)


def exposed_collective_ns(trace: Trace, dev) -> int:
    """Time in the window during which a collective runs on ``dev`` and
    no other operation does."""
    lo, hi = trace.window
    evs = trace.leaves(dev)
    coll = merge([x for x in evs if _collective(x[0])], lo, hi)
    other = merge([x for x in evs if not _collective(x[0])], lo, hi)
    return measure(coll) - _intersect(coll, other)


def idle_gaps(trace: Trace, dev, top: int = 10) -> list:
    """The longest gaps between operations on ``dev`` in the window, each
    named by the benchmark's host span that covers most of it."""
    lo, hi = trace.window
    busy = merge(trace.ops.get(dev, []), lo, hi)
    edges = [lo] + [x for se in busy for x in se] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [h for h in trace.host if h[0] != WINDOW_SPAN]
    out = []
    for s, e in gaps[:top]:
        best, cover = "host", 0
        for name, hs, he in spans:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = name, c
        out.append([best, (e - s) / 1e9])
    return out


def top_ops(trace: Trace, top: int = 10) -> list:
    """The device operations that took most time in the window, summed
    over devices under their names without numeric suffixes; a loop
    counts only through the operations of its body."""
    lo, hi = trace.window
    total: dict = {}
    for dev in trace.ops:
        for n, s, e in trace.leaves(dev):
            if s >= lo and e <= hi:
                key = _SUFFIX.sub("", n)
                total[key] = total.get(key, 0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in ranked]
