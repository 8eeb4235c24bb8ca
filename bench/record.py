"""What the harness records of one run, on the host clock, from its own
wrappers around the engine's calls (the program has no spans of its own).

``Recorder.attach`` wraps the engine's ``_bucket_prefill``, ``_decode``,
``_sample`` and ``_emit``.  Every emitted token is stamped; each prefill
batch and decode tick keeps its span.  In a traced run every wrapper also
opens a ``jax.profiler.TraceAnnotation`` named ``bench.<call>``, and the
prefill and decode spans wait for the device (``block_until_ready``), so
they time the device's work; untraced runs add no synchronisation.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import jax

from .timeline import ReqRec


@dataclasses.dataclass
class PrefillRec:
    t0: float
    t1: float
    bucket: int
    rows: int                  # slots x bucket token rows run
    requests: int = 0          # real requests in the batch
    tokens: int = 0            # their prompt tokens


@dataclasses.dataclass
class TickRec:
    t0: float
    t1: float
    active: int                # slots holding a request


class Recorder:
    def __init__(self, traced: bool):
        self.traced = traced
        self.origin: float | None = None
        self.reqs: list[ReqRec] = []
        self._by_req: dict[int, ReqRec] = {}
        self.prefills: list[PrefillRec] = []
        self.ticks: list[TickRec] = []
        self.samples: list[tuple[float, float]] = []
        self.steps: list[tuple[float, float]] = []
        self.gc_pauses: list[tuple[float, float]] = []
        self._gc_t0 = None

    def span(self, name: str):
        if self.traced:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def start(self) -> None:
        """Open the window: times count from here; nothing before is kept."""
        self.origin = time.perf_counter()
        for spans in (self.prefills, self.ticks, self.samples, self.steps,
                      self.gc_pauses):
            spans.clear()
        gc.callbacks.append(self._on_gc)

    def stop(self) -> None:
        """Close the window's records."""
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, _info) -> None:
        if phase == "start":
            self._gc_t0 = self.now()
        elif self._gc_t0 is not None:
            self.gc_pauses.append((self._gc_t0, self.now()))

    def step(self, engine) -> None:
        """One ``engine.step()``, its span kept."""
        t0 = self.now()
        with self.span("bench.step"):
            engine.step()
        self.steps.append((t0, self.now()))

    def longest_step(self) -> dict:
        """The window's longest engine step, and how much of it went to
        each recorded call and to the garbage collector: where a stall
        lies."""
        if not self.steps:
            return {}
        t0, t1 = max(self.steps, key=lambda s: s[1] - s[0])

        def inside(spans):
            return sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in spans)

        return {"at_s": t0, "ms": (t1 - t0) * 1e3,
                "prefill_ms": inside([(p.t0, p.t1)
                                      for p in self.prefills]) * 1e3,
                "prefill_rows": sum(p.rows for p in self.prefills
                                    if t0 <= p.t0 < t1),
                "decode_ms": inside([(d.t0, d.t1) for d in self.ticks]) * 1e3,
                "sample_ms": inside(self.samples) * 1e3,
                "gc_ms": inside(self.gc_pauses) * 1e3}

    def track(self, req, *, arrival: float, prompt_len: int,
              max_new: int) -> ReqRec:
        rec = ReqRec(rid=req.rid, arrival=arrival, prompt_len=prompt_len,
                     max_new=max_new, request=req)
        self.reqs.append(rec)
        self._by_req[id(req)] = rec
        return rec

    def attach(self, engine) -> None:
        prefill, decode = engine._bucket_prefill, engine._decode
        sample, emit = engine._sample, engine._emit

        def timed(fn, name, on_done):
            def call(*args, **kwargs):
                if self.origin is None:
                    return fn(*args, **kwargs)
                t0 = self.now()
                with self.span(name):
                    out = fn(*args, **kwargs)
                    if self.traced:
                        jax.block_until_ready(out)
                on_done(t0, self.now(), kwargs)
                return out
            return call

        def on_prefill(t0, t1, kw):
            tokens = kw["batch"]["tokens"]
            self.prefills.append(PrefillRec(t0, t1, int(tokens.shape[1]),
                                            int(tokens.size)))

        def on_decode(t0, t1, kw):
            active = sum(r is not None for r in engine.active)
            self.ticks.append(TickRec(t0, t1, active))

        def sampled(logits, req):
            if self.origin is None:
                return sample(logits, req)
            t0 = self.now()
            with self.span("bench.sample"):
                out = sample(logits, req)
            self.samples.append((t0, self.now()))
            return out

        def emitted(req, tok):
            emit(req, tok)
            rec = self._by_req.get(id(req))
            if rec is None or self.origin is None:
                return
            rec.token_times.append(self.now())
            if rec.slot is None:
                rec.slot = next((i for i, a in enumerate(engine.active)
                                 if a is req), None)
            if len(rec.token_times) == 1 and self.prefills:
                last = self.prefills[-1]
                rec.prefill_start = last.t0
                last.requests += 1
                last.tokens += rec.prompt_len

        engine._bucket_prefill = timed(prefill, "bench.prefill", on_prefill)
        engine._decode = timed(decode, "bench.decode", on_decode)
        engine._sample = sampled
        engine._emit = emitted
