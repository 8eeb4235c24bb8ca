"""End-to-end metrics from the recorded timeline of one run.

Times are seconds on the host clock, measured from the first due arrival
(the window's start).  A request's clock starts at its scheduled arrival,
never at its submission, so a stall that delays submission is counted.
Every token is stamped when the engine emits it.  Tails and rates are taken
over the whole window.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class ReqRec:
    """What the harness saw of one request."""
    rid: int
    arrival: float                  # scheduled arrival (backlog: submitted)
    prompt_len: int
    max_new: int
    submitted: float | None = None
    prefill_start: float | None = None
    slot: int | None = None         # the engine's slot that served it
    token_times: list = dataclasses.field(default_factory=list)
    refused: bool = False           # Overloaded at submit
    dropped: bool = False           # shed or timed out by the engine
    done: bool = False
    request: object = None          # the engine's Request (served tokens)

    @property
    def failed(self) -> bool:
        return self.refused or self.dropped


def due(reqs: list[ReqRec], seconds: float) -> list[ReqRec]:
    """Requests whose scheduled arrival falls inside the window."""
    return [r for r in reqs if 0.0 <= r.arrival < seconds]


def _ttft(reqs: list[ReqRec], seconds: float, end: float) -> list[float]:
    """Scheduled arrival to first token of each request due in the window.
    A request that never got a first token counts with its wait until
    ``end`` (when the run stopped waiting), a lower bound of its latency."""
    return [(r.token_times[0] if r.token_times else end) - r.arrival
            for r in due(reqs, seconds)]


def ttft_p90_ms(reqs: list[ReqRec], seconds: float, end: float) -> float:
    """90th percentile of time to first token over the requests due."""
    return float(np.percentile(_ttft(reqs, seconds, end), 90)) * 1e3


def ttft_p50_ms(reqs: list[ReqRec], seconds: float, end: float) -> float:
    return float(np.percentile(_ttft(reqs, seconds, end), 50)) * 1e3


def itl_gaps(reqs: list[ReqRec], seconds: float) -> list[np.ndarray]:
    """Per request due in the window, its token times' gaps' (start, end)."""
    out = []
    for r in due(reqs, seconds):
        t = np.asarray(r.token_times)
        if t.size > 1:
            out.append(np.stack([t[:-1], t[1:]], axis=1))
    return out


def itl_ms(reqs: list[ReqRec], seconds: float, q: float) -> float:
    """``q``-th percentile over every gap between consecutive emitted
    tokens of the requests due in the window."""
    spans = itl_gaps(reqs, seconds)
    if not spans:
        return 0.0
    g = np.concatenate(spans)
    return float(np.percentile(g[:, 1] - g[:, 0], q)) * 1e3


def itl_p95_ms(reqs: list[ReqRec], seconds: float) -> float:
    return itl_ms(reqs, seconds, 95)


def tokens_per_s(reqs: list[ReqRec], seconds: float) -> float:
    """Prompt tokens prefilled (the real ones; padding and re-prefills
    count nothing) plus tokens emitted, inside [0, seconds), per second.
    A prompt counts when its first token is emitted."""
    n = 0
    for r in reqs:
        times = np.asarray(r.token_times)
        n += int(np.count_nonzero((times >= 0) & (times < seconds)))
        if r.token_times and 0 <= r.token_times[0] < seconds:
            n += r.prompt_len
    return n / seconds


def lateness_ms(reqs: list[ReqRec]) -> tuple[float, float]:
    """(median, max) of how late the generator submitted after the
    scheduled arrival."""
    late = [r.submitted - r.arrival for r in reqs if r.submitted is not None]
    if not late:
        return 0.0, 0.0
    return float(np.median(late)) * 1e3, float(np.max(late)) * 1e3
