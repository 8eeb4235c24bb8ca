"""Share of the planner's GEMM decisions (``plan_mode_stats()`` after
set-up) served from measured or cached plans rather than the analytic
model.  Moves ``tokens_per_s``."""


def read(run):
    cached = total = 0
    for family, modes in run.plan_modes.items():
        if family in ("epilogue", "degraded"):
            continue
        for mode, n in modes.items():
            if mode == "quarantined":
                continue
            total += n
            cached += n if mode in ("cached", "measured") else 0
    return 100.0 * cached / total if total else None
