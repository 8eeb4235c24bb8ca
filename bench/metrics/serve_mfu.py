"""Model FLOPs of the real tokens served in the traced slice over the
slice's length times the chip's peak bf16 FLOP/s.  A prompt counts when its
first token is emitted, each generated token at its own depth (the
configuration's model module's counts); padding counts nothing.  Only
tokens of engine calls that started inside the slice count.  Moves
``tokens_per_s``."""
from bench import spec


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    lo, hi = run.slice
    starts = [c.t0 for c in run.ticks + run.prefills if c.t0 >= lo]
    if not starts:
        return None
    first = min(starts)
    cfg, total = run.config, 0.0
    model = spec.model_module(cfg)
    for r in run.reqs:
        for j, t in enumerate(r.token_times):
            if not first < t <= hi:
                continue
            if j == 0:
                total += model.prefill_model_flops(cfg, r.prompt_len)
            else:
                total += model.decode_model_flops(cfg, r.prompt_len + j - 1)
    if total == 0:
        return None
    return 100.0 * total / (run.trace.window_s * run.peaks["bf16_flops"])
