"""Median device-synced span of the engine's fused decode step
(``_decode``) over the window.  Moves ``itl_p95_ms``."""
import statistics


def read(run):
    ticks = [t.t1 - t.t0 for t in run.ticks]
    return statistics.median(ticks) * 1e3 if ticks else None
