#!/usr/bin/env python3
"""The program's own measurement points in a profiler trace: the serving
engine's ``serve.*`` spans and the model's named scopes.

``bench/trace.py`` reduces a trace to the harness's ``bench.*`` spans and
the device ops by HLO category.  This module keeps what that reduction
leaves out:

* ``serve.*`` host spans (``repro.runtime.spans``), in
  ``ProgramTrace.program``, apart from the ``bench.*`` spans;
* each device op's scope path: the ``op_name`` of its HLO metadata, which
  ``jax.named_scope`` writes (``.../while/body/attn/qkv/cast/
  convert_element_type``) and the profile keeps as the ``tf_op`` stat of
  the op's event metadata.  ``ProfileData`` does not hand out metadata
  stats, so ``op_scopes`` reads them from the serialized trace itself.

Two shares are read from them:

* ``sample_idle_share``: the idle seconds of the first chip that fall to
  ``serve.sample`` (each idle gap goes whole to the ``serve.*`` span that
  overlaps it most, ``trace.idle_by_span``'s rule), over the slice;
* ``weight_cast_share``: the device time of the weight casts over the
  device time of every op but the loops.  A weight cast is an op with a
  ``cast`` component in its scope path, or one XLA hoisted out of the
  layer loop: the stacked weights' f32 -> bf16 ``convert`` loses its
  metadata there (and the embedding table's layout ``copy`` carries only
  the parameter's name), so those are told by their HLO text, an f32
  ``params`` argument read into a bf16 result.

Run on a trace directory (``jax.profiler.trace(dir)``, or a capture
through ``jax.profiler.start_server``) it prints both shares, the device
time by scope and the spans per engine step as one JSON object:

    python3 bench/program_trace.py <trace dir>
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

PROGRAM = "serve."
SAMPLE = "serve.sample"
STEP = "serve.step"
# Spans that hold finer ones: an idle gap falls to them only where no
# finer span overlaps it (the admit span before the step).
OUTER = ("serve.admit", STEP)
CAST = "cast"
SCOPE_STAT = "tf_op"
# An f32 argument of the ``params`` tree read straight into a bf16 result.
_PARAM_CAST = re.compile(
    r"= bf16\[[^\]]*\]\S* \w+\(f32\[[^\]]*\]\S* %params__")
# Scope components of the model and the page insert, in the order the
# breakdown prints them.
SCOPES = ("embed", "attn", "qkv", "core", "out", "mlp", "moe", "router",
          "dispatch", "experts", "combine", "final_norm", "lm_head", "cast",
          "kv_insert")


@dataclasses.dataclass
class Op(trace.Event):
    scope: str = ""         # scope path of the op's HLO metadata
    param_cast: bool = False    # reads an f32 weight into a bf16 result


@dataclasses.dataclass
class ProgramTrace:
    trace: trace.Trace      # device ops as ``Op``, bench.* spans, slice
    program: list           # host Events named serve.*


# ------------------------- serialized trace --------------------------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """(field number, value) of one protobuf message; a length-delimited
    value is its (start, end) in ``buf``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_scopes(xspace: bytes) -> dict[str, str]:
    """HLO text of each device op -> the ``tf_op`` stat of its event
    metadata, read from a serialized ``XSpace`` (fields of
    ``tsl/profiler/protobuf/xplane.proto``)."""
    out: dict[str, str] = {}
    for num, plane in _fields(xspace):
        if num != 1:                                   # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(xspace, *plane):
            if f == 2:                                 # XPlane.name
                name = _text(xspace, v)
            elif f == 4:                               # event_metadata
                metas += [e for k, e in _fields(xspace, *v) if k == 2]
            elif f == 5:                               # stat_metadata
                entry = dict(_fields(xspace, *v))
                if 2 in entry:
                    sm = dict(_fields(xspace, *entry[2]))
                    stat_names[sm.get(1, 0)] = _text(xspace,
                                                     sm.get(2, (0, 0)))
        if not name.startswith("/device:"):
            continue
        wanted = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        for meta in metas:
            op, scope = "", ""
            for f, v in _fields(xspace, *meta):
                if f == 2:                             # XEventMetadata.name
                    op = _text(xspace, v)
                elif f == 5:                           # XEventMetadata.stats
                    stat = dict(_fields(xspace, *v))
                    if stat.get(1) not in wanted:
                        continue
                    if 5 in stat:                      # str_value
                        scope = _text(xspace, stat[5])
                    elif 7 in stat:                    # ref_value
                        scope = stat_names.get(stat[7], "")
            if op and scope:
                out.setdefault(op, scope)
    return out


# ------------------------------ reduction ----------------------------------

def from_profile(profile, scopes: dict[str, str]) -> ProgramTrace:
    """A ``jax.profiler.ProfileData`` reduced to the device ops (with their
    scope paths), the ``bench.*`` and the ``serve.*`` host spans.  The
    slice is ``bench.slice``, else the extent of the ``serve.step`` spans."""
    devices: dict = {}
    spans: list = []
    program: list = []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith(
                "/device:CUSTOM"):
            ops = [Op(trace.op_name(e.name), e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9,
                      trace.op_kind(e.name), scopes.get(e.name, ""),
                      bool(_PARAM_CAST.search(e.name)))
                   for line in plane.lines if line.name == trace.OP_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = trace.Event(e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9)
                    if e.name.startswith("bench."):
                        spans.append(ev)
                    elif e.name.startswith(PROGRAM):
                        program.append(ev)
    program.sort(key=lambda s: s.start)
    cut = [s for s in spans if s.name == trace.SLICE]
    steps = [s for s in program if s.name == STEP]
    if cut:
        window = (cut[0].start, cut[0].end)
    elif steps:
        window = (steps[0].start, max(s.end for s in steps))
    else:
        raise ValueError("trace holds neither bench.slice nor serve.step")
    return ProgramTrace(trace.Trace(devices, spans, window), program)


def load(trace_dir: str) -> ProgramTrace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        raw = f.read()
    return from_profile(ProfileData.from_serialized_xspace(raw),
                        op_scopes(raw))


def idle_by_program_span(pt: ProgramTrace) -> dict[str, float]:
    """Idle seconds of the slice on the first chip, each gap put down whole
    to the ``serve.*`` span that overlaps it most (``serve.admit``, then
    ``serve.step``, only where no finer span does): the rule of
    ``trace.idle_by_span`` on the program's spans."""
    groups = [sorted((s for s in pt.program if s.name not in OUTER),
                     key=lambda s: s.start)]
    groups += [[s for s in pt.program if s.name == name] for name in OUTER]
    starts = [[s.start for s in g] for g in groups]
    tot: dict = collections.defaultdict(float)
    for lo, hi in trace.idle_gaps(pt.trace):
        best = None
        for spans, at in zip(groups, starts):
            best = max(trace._overlapping(spans, at, lo, hi),
                       key=lambda so: so[1], default=None)
            if best is not None:
                break
        tot[best[0].name if best else "no serve span"] += hi - lo
    return dict(tot)


def components(scope: str) -> list[str]:
    """The scope path's components, the op itself (its last part) left
    out."""
    return scope.split("/")[:-1]


def time_by_scope(pt: ProgramTrace) -> dict[str, float]:
    """Device seconds of the slice's ops by scope component (an op counts
    once under each component of its path), summed over the chips; loops
    left out: their time is their body's.  ``""`` holds the total."""
    lo, hi = pt.trace.slice
    tot: dict = collections.defaultdict(float)
    for ops in pt.trace.devices.values():
        for e, a, b in trace._clip(ops, lo, hi):
            if e.category in trace.CONTAINERS:
                continue
            tot[""] += b - a
            for c in set(components(e.scope)):
                tot[c] += b - a
    return dict(tot)


def sample_idle_share(pt: ProgramTrace) -> float | None:
    """% of the slice the first chip idles in gaps that fall to
    ``serve.sample``; None where the trace holds no such span."""
    if not any(s.name == SAMPLE for s in pt.program) or \
            pt.trace.window_s <= 0:
        return None
    return (100.0 * idle_by_program_span(pt).get(SAMPLE, 0.0)
            / pt.trace.window_s)


def cast_s(pt: ProgramTrace) -> tuple[float, float]:
    """Device seconds of the slice's weight casts, summed over the chips:
    (scoped ``cast``, hoisted out of their scope)."""
    lo, hi = pt.trace.slice
    scoped = hoisted = 0.0
    for ops in pt.trace.devices.values():
        for e, a, b in trace._clip(ops, lo, hi):
            if CAST in components(e.scope):
                scoped += b - a
            elif e.param_cast:
                hoisted += b - a
    return scoped, hoisted


def weight_cast_share(pt: ProgramTrace) -> float | None:
    """% of the slice's device time (loops left out) spent casting weights;
    None where no op carries the ``cast`` scope (a program without it)."""
    scoped, hoisted = cast_s(pt)
    total = time_by_scope(pt).get("", 0.0)
    if not scoped or not total:
        return None
    return 100.0 * (scoped + hoisted) / total


def gemm_by_scope(pt: ProgramTrace) -> dict[str, float]:
    """Device seconds of the slice's GEMM ops (``trace.is_gemm``) by scope
    component, summed over the chips."""
    lo, hi = pt.trace.slice
    tot: dict = collections.defaultdict(float)
    for ops in pt.trace.devices.values():
        for e, a, b in trace._clip(ops, lo, hi):
            if trace.is_gemm(e):
                for c in set(components(e.scope)):
                    tot[c] += b - a
    return dict(tot)


def summary(pt: ProgramTrace) -> dict:
    """What the CLI prints: both shares, the device time by scope (all ops
    and GEMMs alone), the ``bench.sample`` idle seconds where the harness
    traced, and the spans of each name per engine step."""
    by, gemm = time_by_scope(pt), gemm_by_scope(pt)
    scoped, hoisted = cast_s(pt)
    lo, hi = pt.trace.slice
    inside = [s for s in pt.program if lo <= s.start < hi]
    steps = sum(s.name == STEP for s in inside)
    counts = collections.Counter(s.name for s in inside)
    return {"window_s": pt.trace.window_s,
            "busy_s": trace.busy_s(pt.trace),
            "sample_idle_share": sample_idle_share(pt),
            "weight_cast_share": weight_cast_share(pt),
            "cast_scoped_s": scoped,
            "cast_hoisted_s": hoisted,
            "idle_by_program_span": idle_by_program_span(pt),
            "device_s": by.get("", 0.0),
            "scope_s": {c: by[c] for c in SCOPES if c in by},
            "outside_scopes_s": by.get("", 0.0) - sum(
                v for c, v in by.items() if c in ("embed", "attn", "mlp",
                                                  "moe", "final_norm",
                                                  "lm_head", "kv_insert")),
            "gemm_scope_s": {c: gemm[c] for c in SCOPES if c in gemm},
            "bench_sample_idle_s": dict(trace.idle_by_span(
                pt.trace, n=100)).get("bench.sample"),
            "steps": steps,
            "spans_per_step": {n: c / steps for n, c in sorted(
                counts.items())} if steps else {}}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[-1].strip(), file=sys.stderr)
        return 2
    print(json.dumps(summary(load(args[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
