"""Device-synced prefill time per 1000 token rows run (padding rows
included), over every bucketed prefill of the window.  Moves
``tokens_per_s``."""


def read(run):
    rows = sum(p.rows for p in run.prefills)
    if not rows:
        return None
    return sum(p.t1 - p.t0 for p in run.prefills) * 1e3 / (rows / 1000)
