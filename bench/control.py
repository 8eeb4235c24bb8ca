#!/usr/bin/env python3
"""Readings that set a cell's correctness limit; run on the chip.

    python3 bench/control.py --workload qwen3-1.7b.chat --seeds 101-112 \
        --seconds 15 [--fault token_altered]

One process runs the cell for each seed as ``run.py`` does (at the cell's
own size and load, with a shorter window) and reads, over the same sample
of served requests, each number ``bench/check.py`` compares: for the
program's served tokens (the lower reading) and for the tokens that the
reference computed in float8 e4m3 puts first (the control: the upper
reading), the control judged by the cell's limits as the program is.
Beside them it reads the bfloat16 witness of the tokens served off the
reference's choice.  With ``--fault`` the program runs with that fault of
``bench/faults.py`` planted, and its own reading is the fault's.  Prints
one JSON line per seed and a summary line; exits non-zero without a TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

NUMBERS = ("max_logit_gap", "tokens_off_share")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(_REPO, "src"), _REPO]


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=("token_altered", "state_unchanged"))
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    from bench import spec
    from bench.harness import measure
    bench = spec.benchmark()
    cell = spec.resolve(bench, args.workload)
    if args.fault:
        from bench.faults import FAULTS
        FAULTS[args.fault](setattr)
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = measure(cell, bench, seed=seed, seconds=args.seconds,
                    traced=False, t_process=t0, devices=devices,
                    control=True)
        w = r["window"]
        row = {"seed": seed, "fault": args.fault,
               "requests": w["requests_compared"],
               "slots": w["slots_compared"], "tokens": w["tokens_compared"],
               **{f"program_{k}": w[k] for k in NUMBERS},
               "program_correct": r["correct"],
               **{f"control_{k}": r["control"][k] for k in NUMBERS},
               "control_correct": r["control"]["correct"],
               "witness": r["witness"],
               "attempted": r["attempted"], "failed": r["failed"],
               "wall_s": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)

    print(json.dumps({
        "workload": args.workload, "fault": args.fault, "seeds": len(rows),
        "program_correct": sum(r["program_correct"] for r in rows),
        "control_correct": sum(r["control_correct"] for r in rows),
        **{k: {"lower": max(r[f"program_{k}"] for r in rows),
               "upper": min(r[f"control_{k}"] for r in rows)}
           for k in NUMBERS}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
