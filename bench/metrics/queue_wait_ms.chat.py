"""Median, over the requests due in the window, of scheduled arrival to the
start of the prefill that admitted the request (the harness's span around
``_bucket_prefill``).  Moves ``ttft_p90_ms``."""
import statistics


def read(run):
    waits = [r.prefill_start - r.arrival for r in run.reqs
             if 0 <= r.arrival < run.seconds and r.prefill_start is not None]
    return statistics.median(waits) * 1e3 if waits else None
