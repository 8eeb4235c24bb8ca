"""Reduction of a JAX profiler trace to device time, GEMM time and idle
gaps, on the trace's own clock.

The trace holds the device's ops (planes ``/device:TPU:<n>``, line
``XLA Ops``) and the harness's host spans (``bench.<call>``
``TraceAnnotation`` events on ``/host:CPU``).  The traced slice is the
``bench.slice`` span; device ops are clipped to it.

* busy: the union of the device-op intervals, averaged over the chips;
* GEMM time: the device time of the ops that implement GEMMs, told apart by
  the HLO text each op event carries: the Pallas kernels' custom calls
  (``custom_call_target="tpu_custom_call"``; every Pallas kernel of the
  program is an ftIMM GEMM) and XLA's own ``convolution`` and ``dot``,
  bare or fused, whatever engine runs them;
* idle gaps: the stretches of the slice with no device op, each put down to
  the host span that overlaps it most (``bench.step`` only where no finer
  span does).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

SLICE = "bench.slice"
OUTER = ("bench.step", SLICE)
OP_LINE = "XLA Ops"
# Ops whose interval holds other ops' (the layer scan's loop): busy time,
# but neither GEMM time nor an op of their own.
CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"=\s.*?\s([a-z][\w-]*)\(")
_NAME = re.compile(r"^%?([\w.-]+?)(\.\d+)?\s=")
_KIND = re.compile(r"kind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_kind(text: str) -> str:
    """The HLO op of an op event's text: ``fusion:kLoop``,
    ``custom-call:tpu_custom_call``, ``convolution``, ``while``, ..."""
    m = _OP.search(text)
    op = m.group(1) if m else ""
    extra = {"fusion": _KIND, "custom-call": _TARGET}.get(op)
    if extra is not None:
        k = extra.search(text)
        op += ":" + (k.group(1) if k else "?")
    return op


def op_name(text: str) -> str:
    """The HLO instruction's name without its numeric suffix."""
    m = _NAME.match(text)
    return m.group(1) if m else text[:40]


@dataclasses.dataclass
class Event:
    name: str               # op: HLO instruction name; span: its name
    start: float            # seconds on the trace clock
    end: float
    category: str = ""      # op: ``op_kind`` of its HLO text


@dataclasses.dataclass
class Trace:
    devices: dict           # plane name -> list[Event] (device ops)
    spans: list             # host Events named bench.*
    slice: tuple            # (start, end) of bench.slice

    @property
    def window_s(self) -> float:
        return self.slice[1] - self.slice[0]


def from_profile(profile) -> Trace:
    """A ``jax.profiler.ProfileData`` reduced to the events used here."""
    devices: dict = {}
    spans: list = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") or (
                plane.name.startswith("/device:")
                and not plane.name.startswith("/device:CUSTOM")):
            ops = [Event(op_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         op_kind(e.name))
                   for line in plane.lines if line.name == OP_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans += [Event(e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                      for line in plane.lines for e in line.events
                      if e.name.startswith("bench.")]
    cut = [s for s in spans if s.name == SLICE]
    if not cut:
        raise ValueError("trace holds no bench.slice span")
    return Trace(devices, spans, (cut[0].start, cut[0].end))


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime)))


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ops, lo: float, hi: float):
    for e in ops:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            yield e, a, b


def busy_s(trace: Trace) -> float:
    """Seconds of the slice in which an op ran, averaged over the chips."""
    lo, hi = trace.slice
    per = [sum(b - a for a, b in union((a, b) for _, a, b in _clip(ops, lo,
                                                                   hi)))
           for ops in trace.devices.values()]
    return sum(per) / len(per) if per else 0.0


def is_gemm(event: Event) -> bool:
    cat = event.category
    if cat == "custom-call:tpu_custom_call" or cat in ("convolution", "dot"):
        return True
    return cat.startswith("fusion") and any(
        k in event.name for k in ("convolution", "dot"))


def gemm_s(trace: Trace) -> float:
    """Device seconds of GEMM ops in the slice, summed over the chips."""
    lo, hi = trace.slice
    return sum(b - a for ops in trace.devices.values()
               for e, a, b in _clip(ops, lo, hi) if is_gemm(e))


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` ops (instruction name and HLO op) that took most device
    time, loops left out: their time is their body's."""
    lo, hi = trace.slice
    tot: dict = defaultdict(float)
    for ops in trace.devices.values():
        for e, a, b in _clip(ops, lo, hi):
            if e.category not in CONTAINERS:
                tot[f"{e.name} [{e.category}]"] += b - a
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace) -> list[tuple[float, float]]:
    """Stretches of the slice with no op on the first chip."""
    lo, hi = trace.slice
    if not trace.devices:
        return [(lo, hi)]
    ops = next(iter(trace.devices.values()))
    busy = union((a, b) for _, a, b in _clip(ops, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _overlapping(spans: list[Event], starts: list[float], lo: float,
                 hi: float):
    """Spans of a start-sorted list of sequential (non-nesting) spans that
    overlap [lo, hi]."""
    i = max(bisect.bisect_left(starts, lo) - 1, 0)
    for s in spans[i:bisect.bisect_right(starts, hi)]:
        ov = min(hi, s.end) - max(lo, s.start)
        if ov > 0:
            yield s, ov


def idle_by_span(trace: Trace, n: int = 10) -> list[list]:
    """Idle seconds of the slice summed by the host span that overlaps each
    gap most (an outer ``bench.step`` only where no inner span does),
    largest first."""
    inner = sorted((s for s in trace.spans if s.name not in OUTER),
                   key=lambda s: s.start)
    outer = sorted((s for s in trace.spans if s.name == OUTER[0]),
                   key=lambda s: s.start)
    inner_starts = [s.start for s in inner]
    outer_starts = [s.start for s in outer]
    tot: dict = defaultdict(float)
    for lo, hi in idle_gaps(trace):
        best = max(_overlapping(inner, inner_starts, lo, hi),
                   key=lambda so: so[1], default=None)
        if best is None:
            best = max(_overlapping(outer, outer_starts, lo, hi),
                       key=lambda so: so[1], default=None)
        tot[best[0].name if best else "no bench span"] += hi - lo
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
