"""The one traffic generator: every mix is a data file of parameters
(``traffic/<mix>.json``) read here.

A mix's schedule is ``requests`` plain random draws from its own
``schedule_seed``: lognormal prompt and output lengths, rounded and clipped
to [min, max], and for an open-loop mix ("poisson") exponential gaps of mean
``1 / rate_per_s`` between arrivals.  So every run of a mix sends the same
schedule, and ``--seed`` draws only the token ids (uniform in [2, vocab)).
Open-loop request 0 arrives at 0; the stream ends after ``requests``.
A "backlog" mix has no arrival times: the driver keeps the engine's queue
``queue_per_slot`` times its slots deep, and request ``k`` takes the sizes
of schedule entry ``k mod requests``.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


def lognormal(rng: np.random.Generator, dist: dict, n: int) -> np.ndarray:
    """``n`` lognormal draws (median, sigma), rounded and clipped."""
    x = rng.lognormal(math.log(dist["median"]), dist["sigma"], n)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Item:
    rid: int
    prompt: np.ndarray          # (S,) int32
    max_new: int
    arrival_s: float | None     # None: backlog, submitted when the queue asks


class Traffic:
    """Request stream of one mix; ``item(k)`` is request ``k``, its tokens
    drawn from ``seed``."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.seed = int(seed)
        self.vocab = int(vocab)
        n = int(mix["requests"])
        rng = np.random.default_rng(int(mix["schedule_seed"]))
        self.prompt_sizes = lognormal(rng, mix["prompt_len"], n)
        self.output_sizes = lognormal(rng, mix["output_len"], n)
        self.open_loop = mix["arrival"] == "poisson"
        if self.open_loop:
            gaps = rng.exponential(1.0 / mix["rate_per_s"], n)
            self.arrivals = np.concatenate([[0.0], np.cumsum(gaps[1:])])
        elif mix["arrival"] != "backlog":
            raise ValueError(f"unknown arrival process {mix['arrival']!r}")

    def item(self, k: int) -> Item:
        i = k % len(self.prompt_sizes)
        rng = np.random.default_rng([self.seed, k])
        prompt = rng.integers(2, self.vocab, int(self.prompt_sizes[i]))
        return Item(rid=k, prompt=prompt.astype(np.int32),
                    max_new=int(self.output_sizes[i]),
                    arrival_s=(float(self.arrivals[k]) if self.open_loop
                               else None))

    def arriving(self, horizon: float) -> list[Item]:
        """Open loop: the requests scheduled before ``horizon`` seconds."""
        n = int(np.searchsorted(self.arrivals, horizon))
        return [self.item(k) for k in range(n)]

    def prompt_lengths(self, horizon: float | None = None) -> list[int]:
        """Every prompt length the run can send (the warm-up's shapes):
        those scheduled before ``horizon`` (open loop), or all."""
        sizes = self.prompt_sizes
        if self.open_loop and horizon is not None:
            sizes = sizes[:int(np.searchsorted(self.arrivals, horizon))]
        return sorted(set(int(x) for x in sizes))
