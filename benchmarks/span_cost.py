#!/usr/bin/env python3
"""Host cost of one serving-path span (``repro.runtime.spans.span``), with
no profiler recording and under ``jax.profiler.trace``.

    PYTHONPATH=src python benchmarks/span_cost.py [--n 200000]

Times the span shapes the engine opens: bare (``serve.sync``), two int
arguments (``serve.sample``), a list of ids (``serve.prefill``), and the
decode span with its counts taken over 32 slots as ``ServeEngine`` takes
them.  Prints one JSON object of microseconds per span, the median of five
repeats; each shape is traced under a profiler trace of its own.  The
numbers are the host CPU's; run it on the machine that serves.
"""
from __future__ import annotations

import argparse
import json
import statistics
import tempfile
import time

import jax

from repro.runtime.spans import span

SLOTS = [object()] * 24 + [None] * 8


def _sync(_i):
    with span("serve.sync"):
        pass


def _sample(i):
    with span("serve.sample", rid=i, slot=3):
        pass


def _prefill(i):
    with span("serve.prefill", bucket=256, rows=8 * 256, tokens=900,
              rids=[i, i + 1, i + 2, i + 3]):
        pass


def _decode(_i):
    with span("serve.decode", active=sum(r is not None for r in SLOTS),
              pages_used=1000 - 600):
        pass


SHAPES = {"sync": _sync, "sample": _sample, "prefill": _prefill,
          "decode": _decode}


def per_span_us(fn, n: int) -> float:
    best = []
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(n):
            fn(i)
        best.append((time.perf_counter() - t0) / n * 1e6)
    return statistics.median(best)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args(argv)
    out = {"off": {k: per_span_us(f, args.n) for k, f in SHAPES.items()},
           "on": {}}
    for k, f in SHAPES.items():
        with tempfile.TemporaryDirectory() as d, jax.profiler.trace(d):
            out["on"][k] = per_span_us(f, args.n // 10)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
