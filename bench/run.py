#!/usr/bin/env python3
"""Benchmark of the serving path on the chip; one run of one cell.

    python3 bench/run.py --workload qwen3-1.7b.chat --seed 1 --seconds 40 --trace 0

The cell, its configuration, traffic and metrics are named in
``BENCHMARK.json`` at the repository root.  ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read from a
profiler trace of a slice of the window and from the harness's spans.  The
last line of standard output is one JSON object; the last lines of standard
error give each number compared beside its limit.  Exits non-zero, and
prints no result, where JAX finds no TPU or fewer chips than the cell asks
for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(_REPO, "src"), _REPO]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec
    bench = spec.benchmark()
    cell = spec.resolve(bench, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); this "
              "benchmark measures the chip only", file=sys.stderr)
        return 2
    if len(devices) < cell.entry["chips"]:
        print(f"bench: {args.workload} needs {cell.entry['chips']} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from bench.harness import measure
    result = measure(cell, bench, seed=args.seed, seconds=args.seconds,
                     traced=bool(args.trace), t_process=T_PROCESS,
                     devices=devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
