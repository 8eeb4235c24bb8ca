#!/usr/bin/env python3
"""Knee sweep of an open-loop cell; run on the chip once, when the cell's
rate is fixed.

    python3 bench/sweep.py --workload qwen3-1.7b.chat --rates 0.5,0.75,1 \
        --schedule-seeds 1,2,3 --seconds 40 --seed 7

One process sets the cell up once (weights, engine, warm-up) and runs the
open loop at each rate of each schedule (the mix's draws under another
``schedule_seed``) in turn.  For each it prints the tails, the requests
finished per second, the requests still queued (not yet admitted) at the
close, and the share of the gaps between tokens that hold a prefill: a
queue that grows through the window marks a rate past the knee.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(_REPO, "src"), _REPO]


def numbers(text: str, kind=float) -> list:
    return [kind(x) for x in text.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, type=numbers)
    ap.add_argument("--schedule-seeds", type=lambda s: numbers(s, int),
                    default=None)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from bench import spec
    cell = spec.resolve(spec.benchmark(), args.workload)
    schedules = args.schedule_seeds or [cell.traffic["schedule_seed"]]
    for row in sweep(cell, args.rates, schedules, args.seconds, args.seed):
        print(json.dumps(row), flush=True)
    return 0


def prefill_gap_share(rec, seconds: float) -> float:
    """Share of the gaps between tokens that overlap a prefill."""
    import numpy as np

    from bench import timeline
    spans = timeline.itl_gaps(rec.reqs, seconds)
    if not spans or not rec.prefills:
        return 0.0
    g = np.concatenate(spans)
    starts = np.array([p.t0 for p in rec.prefills])
    ends = np.array([p.t1 for p in rec.prefills])
    # A gap holds a prefill that starts before the gap ends and ends after
    # it starts; prefills do not overlap one another.
    i = np.searchsorted(starts, g[:, 1]) - 1
    held = (i >= 0) & (ends[np.maximum(i, 0)] > g[:, 0])
    return float(held.mean())


def sweep(cell, rates, schedules, seconds: float, seed: int):
    """Yield the set-up time, then one row per schedule and rate."""
    import jax

    from bench import harness, timeline
    from bench.record import Recorder
    from bench.traffic import Traffic
    from bench.weights import make_weights
    from repro.launch.serve import load_plan_cache
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serve.engine import ServeEngine

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    conf, eng = cell.config, cell.engine
    cfg = harness.model_config(conf)
    load_plan_cache(None)
    engine = ServeEngine(cfg, make_weights(conf, seed),
                         batch_slots=eng["slots"], max_len=eng["max_len"])
    rec = Recorder(False)
    rec.attach(engine)

    def traffic(schedule, rate):
        return Traffic(dict(cell.traffic, rate_per_s=rate,
                            schedule_seed=schedule), seed,
                       conf["vocab_size"])

    lengths = set()
    for s in schedules:     # the fastest rate reaches furthest in
        lengths |= set(traffic(s, max(rates)).prompt_lengths(seconds))
    harness.warm_up(engine, conf, sorted(lengths))
    yield {"setup_s": time.perf_counter() - T_PROCESS}
    for schedule in schedules:
        for rate in rates:
            items = traffic(schedule, rate).arriving(seconds)
            rec.reqs.clear()
            # Only the window: stop at the close to read the queue left.
            end = harness.drive_open_loop(engine, rec, items, seconds,
                                          drain_cap=0.0)
            due = timeline.due(rec.reqs, seconds)
            done = sum(r.request.done for r in due)
            yield {
                "schedule_seed": schedule, "rate": rate, "due": len(due),
                "finished": done, "finished_per_s": done / seconds,
                "queued_at_close": len(engine.queue),
                "ttft_p50_ms": timeline.ttft_p50_ms(rec.reqs, seconds, end),
                "ttft_p90_ms": timeline.ttft_p90_ms(rec.reqs, seconds, end),
                **{f"itl_p{q}_ms": timeline.itl_ms(rec.reqs, seconds, q)
                   for q in (50, 90, 95, 99)},
                "prefill_gap_share": prefill_gap_share(rec, seconds),
                "tokens_per_s": timeline.tokens_per_s(rec.reqs, seconds)}
            # Empty the engine before the next run.
            engine.queue.clear()
            while any(r is not None for r in engine.active):
                engine.step()


if __name__ == "__main__":
    sys.exit(main())
