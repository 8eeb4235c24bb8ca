"""GEMMs' share of their roofline in the traced slice: the least time the
chip needs for the GEMMs of the prefills and decode ticks that ran wholly
inside the slice (the configuration's model module's counts at the called
shapes, ``bench/flops.py``'s roofline, the peaks of ``bench/peaks.py``),
over the device time of the trace's GEMM ops (``bench/trace.py``).  Moves
``tokens_per_s``."""
from bench import flops, spec, trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.slice
    cfg, calls = run.config, []
    model = spec.model_module(cfg)
    for p in run.prefills:
        if lo <= p.t0 and p.t1 <= hi:
            calls += model.prefill_gemms(cfg, run.slots, p.bucket)
    for t in run.ticks:
        if lo <= t.t0 and t.t1 <= hi:
            calls += model.decode_gemms(cfg, run.slots, run.view_len)
    busy = trace.gemm_s(run.trace)
    if not calls or busy <= 0:
        return None
    least = flops.roofline_seconds(calls, run.peaks["bf16_flops"],
                                   run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
