"""One benchmark run of one cell, through the program's normal serving path:
``load_plan_cache`` -> seeded weights on the device -> ``ServeEngine``
(bucketed prefill, paged decode, the planner, the Pallas ftIMM kernels),
driven by ``submit`` / ``step`` from the cell's traffic.

``measure`` makes a run and returns the result line's fields; ``run.py``
is the command around it.  Set-up runs from the start of the process to
the first due arrival and warms every shape the cell's traffic uses:
the prefill bucket of each prompt length the mix can send, the state insert
of each such length (the model module's ``warm_insert``: the page insert of
the decoders), the sampler and the decode step.
"""
from __future__ import annotations

import dataclasses
import gc
import tempfile
import time

import numpy as np

from . import check, spec, timeline, trace as trace_mod
from .peaks import peaks_for
from .record import Recorder
from .traffic import Traffic

DRAIN_CAP_S = 60.0          # how long past the close due requests may take
LOAD_AFTER_S = 15.0         # open loop: arrivals go on this long past the close
TRACE_AT = 1 / 3            # traced slice starts this far into the window
TRACE_S = 3.0               # and lasts this long (at most)


def compile_counter() -> dict:
    """Running totals of XLA backend compiles (count, seconds) and
    persistent-cache hits, fed by JAX's monitoring events."""
    import jax
    totals = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            totals["compiles"] += 1
            totals["compile_s"] += duration

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return totals


def model_config(cfg: dict):
    """The program's ``ModelConfig`` from a configuration file: its keys that
    are fields of ``ModelConfig``, taken as they are."""
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    if "window_pattern" in kw:
        kw["window_pattern"] = tuple(kw["window_pattern"])
    return ModelConfig(**kw)


@dataclasses.dataclass
class RunRecord:
    """Everything a per-layer metric reader may read."""
    config: dict                 # the configuration file
    slots: int
    seconds: float
    view_len: int                # keys in a decode tick's paged view
    peaks: dict
    reqs: list
    prefills: list
    ticks: list
    setup_compile_s: float
    plan_modes: dict
    slice: tuple | None = None   # traced slice on the window's clock
    trace: object = None         # trace.Trace of the slice


def warm_up(engine, conf: dict, lengths: list[int]) -> None:
    """Compile and load every program the window will call."""
    from repro.serve.buckets import bucket_for
    from repro.serve.engine import Request

    by_bucket: dict[int, list[int]] = {}
    for n in lengths:
        by_bucket.setdefault(bucket_for(n, engine.buckets), []).append(n)
    # One request per bucket: prefill, insert, sampler, decode step.
    engine.run([Request(rid=-1 - i, prompt=np.full(ls[0], 2, np.int32),
                        max_new_tokens=2)
                for i, ls in enumerate(by_bucket.values())])
    # The state insert runs at each prompt length's shape.
    model = spec.model_module(conf)
    for bucket, ls in by_bucket.items():
        model.warm_insert(engine, conf, bucket, ls)
    import jax
    jax.block_until_ready(engine.kv.k)


class _Tracer:
    """Profiles one slice of the window into a temporary directory."""

    def __init__(self, rec: Recorder, start: float, length: float):
        self.rec, self.start, self.length = rec, start, length
        self.dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        self.on = False
        self.done = False
        self.t0 = self.t1 = None
        self._span = None

    def poll(self, now: float) -> None:
        import jax
        if not self.on and not self.done and now >= self.start:
            jax.profiler.start_trace(self.dir.name)
            self._span = jax.profiler.TraceAnnotation(trace_mod.SLICE)
            self._span.__enter__()
            self.t0, self.on = self.rec.now(), True
        elif self.on and now >= self.t0 + self.length:
            self.stop()

    def stop(self) -> None:
        import jax
        if not self.on:
            return
        self.t1 = self.rec.now()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on, self.done = False, True

    def read(self):
        try:
            return trace_mod.load(self.dir.name)
        finally:
            self.dir.cleanup()


def _submit(engine, rec: Recorder, item, arrival: float):
    from repro.serve.engine import Overloaded, Request
    req = Request(rid=item.rid, prompt=item.prompt,
                  max_new_tokens=item.max_new)
    r = rec.track(req, arrival=arrival, prompt_len=len(item.prompt),
                  max_new=item.max_new)
    r.submitted = rec.now()
    try:
        engine.submit(req)
    except Overloaded:
        r.refused = True
    return r


def _busy(engine) -> bool:
    return bool(engine.queue) or any(r is not None for r in engine.active)


def drive_open_loop(engine, rec: Recorder, items, seconds: float,
                    tracer=None, drain_cap: float = DRAIN_CAP_S) -> float:
    """Submit each request at its scheduled arrival and step the engine;
    after the close keep the schedule going until every due request has
    finished (at most ``drain_cap`` seconds).  Returns when it stopped."""
    import jax
    k, due = 0, [it for it in items if it.arrival_s < seconds]
    recs = []
    rec.start()
    while True:
        now = rec.now()
        while k < len(items) and items[k].arrival_s <= now:
            recs.append(_submit(engine, rec, items[k], items[k].arrival_s))
            k += 1
        if tracer is not None:
            tracer.poll(now)
        if now >= seconds and k >= len(due) and all(
                r.request.done or r.failed for r in recs[:len(due)]):
            break
        if now >= seconds + drain_cap:
            break
        if _busy(engine):
            rec.step(engine)
        elif k < len(items):
            with rec.span("bench.wait"):
                time.sleep(max(0.0, min(items[k].arrival_s - rec.now(),
                                        0.05)))
        else:
            break
    if tracer is not None:
        tracer.stop()
    rec.stop()
    jax.block_until_ready(engine.kv.k)
    return rec.now()


def drive_backlog(engine, rec: Recorder, traffic: Traffic, seconds: float,
                  depth: int, tracer=None) -> float:
    """Keep ``depth`` requests queued and step the engine until the close."""
    import jax
    k = 0
    rec.start()
    while True:
        now = rec.now()
        if now >= seconds:
            break
        if tracer is not None:
            tracer.poll(now)
        while len(engine.queue) < depth:
            _submit(engine, rec, traffic.item(k), rec.now())
            k += 1
        rec.step(engine)
    if tracer is not None:
        tracer.stop()
    rec.stop()
    jax.block_until_ready(engine.kv.k)
    return rec.now()


def end_to_end(names: list[str], reqs, seconds: float, end: float) -> dict:
    fns = {"ttft_p90_ms": lambda: timeline.ttft_p90_ms(reqs, seconds, end),
           "itl_p95_ms": lambda: timeline.itl_p95_ms(reqs, seconds),
           "tokens_per_s": lambda: timeline.tokens_per_s(reqs, seconds)}
    return {n: fns[n]() for n in names if n in fns}


def measure(cell: spec.CellSpec, bench: dict, *, seed: int, seconds: float,
            traced: bool, t_process: float, devices,
            control: bool = False) -> dict:
    """One run of ``cell``; returns the fields of the result line.
    ``control`` also reads the float8 control, judged by the same limits,
    and the bfloat16 witness (``bench/control.py``; the benchmark's own
    runs never do)."""
    import jax

    from repro.core.gemm import plan_mode_stats
    from repro.launch.serve import load_plan_cache
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serve.engine import ServeEngine

    from .weights import make_weights

    counter = compile_counter()
    enable_compile_cache()
    # Small programs (the page insert at each prompt length) are cached too,
    # so that a warm run loads them instead of compiling them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = peaks_for(devices[0].device_kind)
    conf, eng, mix = cell.config, cell.engine, cell.traffic
    cfg = model_config(conf)
    slots, max_len = eng["slots"], eng["max_len"]

    load_plan_cache(None)
    params = make_weights(conf, seed)
    engine = ServeEngine(cfg, params, batch_slots=slots, max_len=max_len,
                         seed=seed)
    traffic = Traffic(mix, seed, conf["vocab_size"])
    rec = Recorder(traced)
    rec.attach(engine)
    warm_up(engine, conf, traffic.prompt_lengths(seconds + LOAD_AFTER_S))
    if traffic.open_loop:
        items = traffic.arriving(seconds + LOAD_AFTER_S)
    plan_modes = plan_mode_stats()
    setup_compile_s = counter["compile_s"]
    before = dict(counter)
    tracer = (_Tracer(rec, seconds * TRACE_AT, min(TRACE_S, seconds / 2))
              if traced else None)
    setup_s = time.perf_counter() - t_process

    if traffic.open_loop:
        end = drive_open_loop(engine, rec, items, seconds, tracer)
    else:
        end = drive_backlog(engine, rec, traffic, seconds,
                            mix["queue_per_slot"] * slots, tracer)
    window = {k: counter[k] - before[k] for k in before}
    for r in rec.reqs:
        r.done = r.request.done
        r.dropped = r.request.shed or r.request.timed_out
    stats = [d.memory_stats() or {} for d in devices[:cell.entry["chips"]]]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)

    if traffic.open_loop:
        counted = timeline.due(rec.reqs, seconds)
        failed = sum(r.failed or not r.done for r in counted)
    else:
        counted = [r for r in rec.reqs if r.token_times]
        failed = sum(r.failed for r in rec.reqs)
    late_p50, late_max = timeline.lateness_ms(counted)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak_bytes)}
    out = {"metrics": {}, "device": device}
    if traced:
        run = RunRecord(config=conf, slots=slots, seconds=seconds,
                        view_len=engine.kv.table.shape[1] * engine.page_size,
                        peaks=peaks, reqs=rec.reqs, prefills=rec.prefills,
                        ticks=rec.ticks, setup_compile_s=setup_compile_s,
                        plan_modes=plan_modes,
                        slice=(tracer.t0, tracer.t1),
                        trace=tracer.read())
        device["busy_s"] = trace_mod.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        for m in spec.per_layer(bench, cell.name):
            value = spec.metric_reader(m["name"])(run)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        out["breakdown"] = {"device_ops": trace_mod.top_ops(run.trace),
                            "idle_gaps": trace_mod.idle_by_span(run.trace)}
    else:
        names = [m["name"] for m in spec.end_to_end(bench, cell.name)]
        units = {m["name"]: m["unit"]
                 for m in spec.end_to_end(bench, cell.name)}
        values = end_to_end(names, rec.reqs, seconds, end)
        values["setup_s"] = setup_s
        out["metrics"] = {n: {"value": values[n], "unit": units[n]}
                          for n in names}

    # The program's state goes before the reference runs; the weights are
    # the benchmark's own data and stay.  The engine sits in a cycle with
    # the recorder's wrappers, so it is emptied by hand and collected.
    engine.close()
    engine.kv = engine.alloc = engine.cost = engine.params = None
    del engine
    gc.collect()
    result = check.compare(params, conf, rec.reqs, seed,
                           eng["check_tokens"], eng["check_requests"],
                           max_len, control=control)
    correct, checks = check.judge(result, eng["limits"], result["tokens"])
    window_info = {"compiles": window["compiles"],
                   "cache_loads": window["cache_hits"],
                   "generator_late_ms_p50": late_p50,
                   "generator_late_ms_max": late_max,
                   "longest_step": rec.longest_step(),
                   "requests_compared": result["requests"],
                   "slots_compared": result["slots"],
                   "tokens_compared": result["tokens"],
                   "max_logit_gap": result["max_logit_gap"],
                   "tokens_off_share": result["tokens_off_share"]}
    if control:
        c_correct, c_checks = check.judge(result["control"], eng["limits"],
                                          result["tokens"])
        out["control"] = {"correct": c_correct, "checks": c_checks,
                          **result["control"]}
        out["witness"] = result["witness"]
    return {"correct": correct, "attempted": len(counted), "failed": failed,
            **out, "window": window_info, "checks": checks}
