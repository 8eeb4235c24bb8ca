"""Overload-safe batched serving engine: bucketed batch prefill, paged KV,
CMR-priced admission control over jitted prefill / decode steps.

The engine owns B decode slots.  For attention-cache families (dense / moe /
vlm) the KV lives in a PAGED pool (``serve.kv_pages``): each request owns
just the pages its depth needs, acquired from a free-list allocator as
decode crosses page boundaries, and a (B, max_pages) page table routes the
fused ``decode_step`` — slot count and sequence length stop being
compile-time constants of the cache.  Prompts are admitted through
LENGTH-BUCKETED batch prefill (``serve.buckets`` / ``prefill_bucket``): a
small geometric ladder of capacities, one compiled prefill per bucket,
right-padding exact by causality.  Recurrent families (ssm / hybrid /
encdec) keep the legacy dense slot cache + exact-length prefill — pad
tokens would contaminate recurrent state.

A single fused ``decode_step`` advances every active slot one token per
tick with PER-SLOT positions, so slots at different depths write and mask
at their own rows.  Sampling is greedy or temperature.  Detokenization
runs on a worker thread consuming a token queue — the decode hot loop
never blocks on string assembly.

Overload safety (chaos-tested; see ``runtime.chaos``):

  * ``submit`` prices each deadline-carrying request against the
    CMR-derived, measurement-calibrated cost model (``serve.buckets``) and
    raises typed ``Overloaded`` when the projected completion cannot meet
    the deadline — rejection at the door, not a hang at the deadline;
  * deadline-infeasible QUEUED work is shed oldest-first as estimates
    move, and expired requests (queued or active) free their resources;
  * KV page exhaustion preempts the lowest-priority active request
    (pages freed, request re-queued for re-prefill of prompt + generated
    tokens — greedy decode makes recovery bit-identical) instead of
    OOMing or wedging; admission never preempts, it waits
    (``page_exhaustion`` site forces this path);
  * a prompt the bucket ladder cannot hold falls back to the legacy
    exact-length jitted prefill (LRU-bounded) and page-inserts
    (``bucket_miss`` site forces the rung);
  * transient decode faults retry with exponential backoff
    (``transient_decode`` site); non-finite logits quarantine the slot —
    pages freed AND ZEROED (a later occupant's ``p @ V`` would contract
    0 * NaN = NaN against poisoned rows) and the request re-prefills
    (``nan_logits`` site).

Decode attention runs as flash-decode (paper K-parallel) whenever a
DistContext is active — see models.attention.flash_decode.

Profiler spans (``runtime.spans``; they record only under a profiler):
``serve.step`` (``active``, ``queue``) holds ``serve.admit`` (``free``),
which holds each ``serve.prefill`` (``bucket``, ``rows`` = slots x bucket,
``tokens``, ``rids``), ``serve.insert`` (``rid``, ``slot``, ``len``),
``serve.sync`` (the blocking read of the logits) and ``serve.sample``
(``rid``, ``slot``, ``on``); then ``serve.decode`` (``active``,
``pages_used``), ``serve.sync`` and one ``serve.sample`` per active slot.
Every token is chosen from the (1, V) logits row already on the host:
greedy rows with numpy's argmax (``on="host"``), temperature rows with a
draw on the device (``on="device"``); ``health()["sampling"]`` counts both.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import queue as _queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models.model import (decode_step, make_cache, prefill,
                            prefill_bucket)
from ..runtime import chaos as _chaos
from ..runtime.spans import span
from .buckets import CostModel, bucket_for, make_buckets
from .kv_pages import PageAllocator, PagedKV, PagesExhausted, pages_for

PAGED_FAMILIES = ("dense", "moe", "vlm")


class Overloaded(RuntimeError):
    """Typed admission rejection: the engine cannot meet this request's
    deadline at current load (or the request cannot fit the KV pool at
    all).  Raised by ``submit`` BEFORE the request consumes anything —
    the caller sheds or re-routes instead of waiting for a timeout."""

    def __init__(self, reason: str, *, projected_s: float | None = None,
                 deadline_s: float | None = None):
        msg = reason
        if projected_s is not None and deadline_s is not None:
            msg += (f" (projected {projected_s:.3f}s"
                    f" > deadline {deadline_s:.3f}s)")
        super().__init__(msg)
        self.reason = reason
        self.projected_s = projected_s
        self.deadline_s = deadline_s


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    deadline_s: float | None = None   # wall-clock budget from submit()
    priority: int = 0             # higher survives page pressure longer
    out_tokens: list = dataclasses.field(default_factory=list)
    text: str = ""                # filled by the detokenize worker
    done: bool = False
    timed_out: bool = False
    shed: bool = False            # dropped by load shedding / admission
    submitted_at: float = 0.0


class _Detokenizer:
    """Worker thread turning emitted token ids into ``Request.text`` off
    the decode hot loop.  The decode tick enqueues (request, token) and
    moves on; ``drain()`` joins the queue at end-of-run."""

    def __init__(self, fn):
        self.fn = fn
        self.q: _queue.Queue = _queue.Queue()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            item = self.q.get()
            if item is None:
                self.q.task_done()
                return
            req, tok = item
            try:
                req.text += self.fn(tok)
            finally:
                self.q.task_done()

    def put(self, req: Request, tok: int) -> None:
        self.q.put((req, tok))

    def drain(self) -> None:
        self.q.join()

    def close(self) -> None:
        self.q.put(None)
        self._thread.join(timeout=5)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 max_len: int = 512, seed: int = 0,
                 prefill_cache_size: int = 8, decode_retries: int = 2,
                 retry_backoff_s: float = 0.02,
                 paged: bool | None = None, page_size: int = 16,
                 num_pages: int | None = None,
                 buckets: tuple[int, ...] | None = None,
                 detokenize=None):
        self.cfg = cfg
        self.params = params
        self.b = batch_slots
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        self.extra = cfg.num_patches or 0
        self.pos = np.zeros(batch_slots, np.int32)       # filled length/slot
        self.active: list[Request | None] = [None] * batch_slots
        self.queue: list[Request] = []
        self._decode = jax.jit(functools.partial(decode_step, cfg=cfg))
        self._prefill_cache: collections.OrderedDict[int, object] = \
            collections.OrderedDict()
        self.prefill_cache_size = prefill_cache_size
        self.decode_retries = decode_retries
        self.retry_backoff_s = retry_backoff_s
        self._detok = _Detokenizer(detokenize) if detokenize else None
        self.faults = {"transient_retries": 0, "deadline_expired": 0,
                       "nonfinite_quarantined": 0, "prefill_evictions": 0,
                       "admission_rejected": 0, "shed": 0,
                       "preemptions": 0, "bucket_misses": 0}
        self.sampling = {"host": 0, "device": 0}

        self.paged = (cfg.family in PAGED_FAMILIES if paged is None
                      else paged)
        if self.paged and cfg.family not in PAGED_FAMILIES:
            raise ValueError(f"paged KV unsupported for {cfg.family}")
        if self.paged:
            depth_cap = max_len + self.extra
            self.page_size = page_size
            self.num_pages = (num_pages if num_pages is not None
                              else batch_slots * pages_for(depth_cap,
                                                           page_size))
            self.alloc = PageAllocator(self.num_pages, first=1)
            # Pool holds the reserved null page 0 in front of the
            # allocatable ids [1, num_pages].
            self.kv = PagedKV.build(cfg, slots=batch_slots,
                                    max_len=depth_cap,
                                    num_pages=self.num_pages + 1,
                                    page_size=page_size)
            self.cache = None
            self.buckets = (tuple(buckets) if buckets
                            else make_buckets(max_len))
            # Constructing the cost model prices every bucket via
            # plan_gemm — which warms the plan cache for exactly the
            # signatures serving will hit.
            self.cost: CostModel | None = CostModel(cfg, self.buckets,
                                                    batch_slots)
            self._bucket_prefill = jax.jit(
                functools.partial(prefill_bucket, cfg=cfg))
            # First call per compiled shape includes trace+compile wall —
            # feeding it to the cost EWMAs would wildly overprice steady
            # state (and with it every admission deadline decision).
            self._timed_buckets: set[int] = set()
            self._timed_step = False
        else:
            self.cache = make_cache(cfg, batch_slots, max_len)
            self.buckets = ()
            self.cost = None
            self.alloc = None
            self.kv = None

    # -------------------------- request plumbing ------------------------

    def _req_tokens(self, req: Request) -> np.ndarray:
        """What a (re-)prefill must run: prompt + everything generated
        so far (preemption / quarantine recovery re-enters here)."""
        if req.out_tokens:
            return np.concatenate([np.asarray(req.prompt, np.int32),
                                   np.asarray(req.out_tokens, np.int32)])
        return np.asarray(req.prompt, np.int32)

    def submit(self, req: Request) -> None:
        """Admit ``req`` to the queue, or raise typed ``Overloaded``.

        Rejection happens only when the request carries a deadline AND the
        cost model has measured wall times to price against (an unpriced
        guess never rejects) — or when the request could never fit the KV
        pool at all."""
        req.submitted_at = time.monotonic()
        if self.paged:
            # Depth is also capped by max_len (decode stops there), so a
            # huge max_new_tokens is not by itself inadmissible.
            worst = pages_for(
                min(len(req.prompt) + req.max_new_tokens, self.max_len)
                + self.extra, self.page_size)
            if worst > self.alloc.total:
                self.faults["admission_rejected"] += 1
                raise Overloaded(
                    f"request needs {worst} KV pages, pool holds "
                    f"{self.alloc.total}")
        if req.deadline_s is not None:
            est = self._projected_completion_s(req)
            if est is not None and est > req.deadline_s:
                self.faults["admission_rejected"] += 1
                raise Overloaded("projected completion misses deadline",
                                 projected_s=est,
                                 deadline_s=req.deadline_s)
        self.queue.append(req)

    def _projected_completion_s(self, req: Request) -> float | None:
        """Estimated seconds until ``req`` would finish if admitted now:
        amortized prefill share + fused-decode share of the backlog ahead
        of it, plus its own service.  None while uncalibrated."""
        if self.cost is None or not self.cost.calibrated():
            return None
        step = self.cost.step_s()
        ahead = sum(max(r.max_new_tokens - len(r.out_tokens), 0)
                    for r in self.active if r is not None)
        ahead += sum(max(r.max_new_tokens - len(r.out_tokens), 0)
                     for r in self.queue)
        pre_backlog = 0.0
        for r in self.queue:
            pre = self.cost.prefill_s(
                bucket_for(len(self._req_tokens(r)), self.buckets))
            pre_backlog += (pre or 0.0) / self.b
        own_pre = self.cost.prefill_s(
            bucket_for(len(self._req_tokens(req)), self.buckets)) or 0.0
        return (pre_backlog + (ahead / self.b) * step + own_pre
                + req.max_new_tokens * step)

    def _prefill_fn(self, s: int):
        """One jitted prefill per exact prompt length, LRU-bounded: the
        legacy rung (recurrent families, bucket misses) must not grow a
        compiled-function cache without bound."""
        fn = self._prefill_cache.get(s)
        if fn is not None:
            self._prefill_cache.move_to_end(s)
            return fn
        fn = jax.jit(functools.partial(prefill, cfg=self.cfg))
        self._prefill_cache[s] = fn
        while len(self._prefill_cache) > self.prefill_cache_size:
            self._prefill_cache.popitem(last=False)
            self.faults["prefill_evictions"] += 1
        return fn

    def _frontend_batch(self, toks: np.ndarray) -> dict:
        batch = {"tokens": jnp.asarray(toks)}
        bsz = toks.shape[0]
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (bsz, self.cfg.encoder_seq, self.cfg.d_model), jnp.float32)
        if self.cfg.num_patches:
            batch["patch_embeds"] = jnp.zeros(
                (bsz, self.cfg.num_patches, self.cfg.d_model), jnp.float32)
        return batch

    def _prefill_one(self, slot: int, req: Request,
                     tokens: np.ndarray | None = None) -> None:
        """Legacy dense-slot prefill (non-paged engines): run ``tokens``
        (default: the prompt) into ``slot``'s cache region and sample one
        continuation token."""
        toks = np.asarray(req.prompt if tokens is None else tokens, np.int32)
        s = len(toks)
        fn = self._prefill_fn(s)
        one_cache = make_cache(self.cfg, 1, self.max_len)
        with span("serve.prefill", bucket=s, rows=s, tokens=s,
                  rids=[req.rid]):
            logits, one_cache = fn(self.params,
                                   batch=self._frontend_batch(toks[None, :]),
                                   cache=one_cache)
        # copy slot cache in
        self.cache = jax.tree.map(
            lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                big, small.astype(big.dtype), slot, axis=1),
            self.cache, one_cache)
        with span("serve.sync"):
            logits = np.asarray(logits)
        self._emit(req, self._sample_slot(slot, logits, req))
        self.pos[slot] = s + self.extra
        self.active[slot] = req

    def _sample_slot(self, slot: int, logits: np.ndarray,
                     req: Request) -> int:
        """``_sample`` for the request in ``slot`` over its (1, V) host
        logits row, inside its ``serve.sample`` span, whose ``on`` names
        where the token is chosen; ``self.sampling`` counts each."""
        on = "host" if req.temperature <= 0 else "device"
        self.sampling[on] += 1
        with span("serve.sample", rid=req.rid, slot=slot, on=on):
            return self._sample(logits, req)

    def _sample(self, logits: np.ndarray, req: Request) -> int:
        """One token from a (1, V) host logits row.  Greedy is numpy's
        argmax, which, like ``jnp.argmax``, takes the lowest index among
        equal maxima; a temperature draw uploads the row and splits the
        engine's key."""
        if req.temperature <= 0:
            return int(np.argmax(logits[0]))
        self.key, sub = jax.random.split(self.key)
        return int(np.asarray(jax.random.categorical(
            sub, jnp.asarray(logits) / req.temperature, axis=-1))[0])

    def _emit(self, req: Request, tok: int) -> None:
        req.out_tokens.append(tok)
        if self._detok is not None:
            self._detok.put(req, tok)

    # ------------------------------ paging -------------------------------

    def _alloc_pages(self, req: Request, n: int, *,
                     active_slot: int | None = None) -> list[int] | None:
        """Acquire ``n`` pages for ``req``, or None if it must wait.

        Admission-time calls (``active_slot`` is None) NEVER preempt —
        an incoming request waits rather than thrashing live decode.
        Decode-growth calls preempt the lowest-priority active victim
        (ties: youngest submitted) with ``priority <= req.priority``;
        when the best victim is ``req`` itself, it yields its own slot.
        The ``page_exhaustion`` chaos site forces the exhaustion branch
        even with free pages."""
        forced = _chaos.should_fire("page_exhaustion") is not None
        while True:
            if forced:
                forced = False
            else:
                try:
                    return self.alloc.alloc(n, id(req))
                except PagesExhausted:
                    pass
            if active_slot is None:
                return None
            victim_slot = self._pick_victim(req)
            if victim_slot is None:
                return None
            self._preempt_slot(victim_slot)
            if victim_slot == active_slot:
                return None           # req preempted itself (yielded)

    def _pick_victim(self, req: Request) -> int | None:
        """Slot of the lowest-priority active request ``req`` may evict
        (priority <= req.priority; ties resolved against the youngest).
        ``req``'s own slot is eligible last — returning it means 'yield'."""
        best = None
        for i, r in enumerate(self.active):
            if r is None or r.priority > req.priority:
                continue
            rank = (r.priority, -r.submitted_at, 1 if r is req else 0)
            if best is None or rank < best[0]:
                best = (rank, i)
        return None if best is None else best[1]

    def _preempt_slot(self, slot: int) -> None:
        """Free a victim's pages and send it back to the queue head for
        re-prefill (prompt + generated-so-far) — pages hold finite values,
        so no zeroing is needed (stale rows are position-masked and weight
        exactly 0 in the next occupant's softmax)."""
        r = self.active[slot]
        self.alloc.free_owner(id(r))
        self.kv.clear_slot(slot)
        self.pos[slot] = 0
        self.active[slot] = None
        self.queue.insert(0, r)
        self.faults["preemptions"] += 1

    def _release_slot(self, slot: int, req: Request) -> None:
        if self.paged:
            self.alloc.free_owner(id(req))
            self.kv.clear_slot(slot)
        self.active[slot] = None
        self.pos[slot] = 0

    def _ensure_pages(self) -> None:
        """Grow each active slot's page span to cover the row this tick's
        decode will write; exhaustion preempts (see ``_alloc_pages``)."""
        for i in range(self.b):
            r = self.active[i]
            if r is None:
                continue
            need = pages_for(int(self.pos[i]) + 1, self.page_size)
            have = len(self.alloc.owned(id(r)))
            if need <= have:
                continue
            pages = self._alloc_pages(r, need - have, active_slot=i)
            if pages is None:
                if self.active[i] is r:     # couldn't grow, didn't yield:
                    self._preempt_slot(i)   # requeue rather than wedge
                continue
            self.kv.extend_slot(i, pages, have)

    # --------------------------- admission -------------------------------

    def _admit(self) -> None:
        with span("serve.admit",
                  free=sum(r is None for r in self.active)):
            self._admit_queued()

    def _admit_queued(self) -> None:
        if not self.paged:
            for slot in range(self.b):
                if self.active[slot] is None and self.queue:
                    req = self.queue.pop(0)
                    self._prefill_one(slot, req,
                                      tokens=self._req_tokens(req))
            return
        while self.queue:
            free = [i for i in range(self.b) if self.active[i] is None]
            if not free:
                return
            head_toks = self._req_tokens(self.queue[0])
            bkt = bucket_for(len(head_toks), self.buckets)
            if _chaos.should_fire("bucket_miss") is not None:
                bkt = None
            if bkt is None:
                self.faults["bucket_misses"] += 1
                req = self.queue.pop(0)
                if not self._admit_exact(free[0], req, head_toks):
                    return
                continue
            batch: list[tuple[Request, np.ndarray]] = []
            while self.queue and len(batch) < len(free):
                toks = self._req_tokens(self.queue[0])
                if bucket_for(len(toks), self.buckets) != bkt:
                    break
                batch.append((self.queue.pop(0), toks))
            if not self._admit_bucket(free, batch, bkt):
                return

    def _admit_exact(self, slot: int, req: Request,
                     toks: np.ndarray) -> bool:
        """Bucket-miss rung: legacy exact-length jitted prefill, then
        page-insert.  False = pool pressure, stop admitting this tick."""
        depth = len(toks) + self.extra
        pages = self._alloc_pages(req, pages_for(depth + 1, self.page_size))
        if pages is None:
            self.queue.insert(0, req)
            return False
        fn = self._prefill_fn(len(toks))
        one_cache = make_cache(self.cfg, 1, len(toks))
        t0 = time.monotonic()
        with span("serve.prefill", bucket=len(toks), rows=len(toks),
                  tokens=len(toks), rids=[req.rid]):
            logits, one_cache = fn(self.params,
                                   batch=self._frontend_batch(toks[None, :]),
                                   cache=one_cache)
        with span("serve.sync"):
            logits = np.asarray(logits)      # sync: the wall we observe
        tok = self._sample_slot(slot, logits, req)
        key = ("exact", len(toks))
        if self.cost is not None and key in self._timed_buckets:
            self.cost.observe_prefill(self.buckets[-1],
                                      time.monotonic() - t0)
        self._timed_buckets.add(key)
        with span("serve.insert", rid=req.rid, slot=slot, len=depth):
            self.kv.insert(slot, pages, one_cache["k"][:, 0, :depth],
                           one_cache["v"][:, 0, :depth])
        self._emit(req, tok)
        self.pos[slot] = depth
        self.active[slot] = req
        return True

    def _admit_bucket(self, free: list[int],
                      batch: list[tuple[Request, np.ndarray]],
                      bkt: int) -> bool:
        """One bucketed batch prefill: every admitted request's padded
        prompt runs through ONE compiled stack pass, each row's KV rows
        page-insert into its slot.  Page allocation happens FIRST (cheap,
        host-side) so an exhausted pool skips the compute; blocked
        requests go back to the queue head.  False = stop admitting."""
        rows: list[tuple[int, Request, np.ndarray, list[int]]] = []
        blocked = False
        for (req, toks) in batch:
            depth = len(toks) + self.extra
            pages = self._alloc_pages(
                req, pages_for(depth + 1, self.page_size))
            if pages is None:
                self.queue.insert(0, req)
                blocked = True
                break
            rows.append((free[len(rows)], req, toks, pages))
        if not rows:
            return not blocked
        toks_pad = np.zeros((self.b, bkt), np.int32)
        lens = np.ones(self.b, np.int32)    # pad rows: 1 token-0 row
        for j, (_, _, toks, _) in enumerate(rows):
            toks_pad[j, :len(toks)] = toks
            lens[j] = len(toks)
        cache = make_cache(self.cfg, self.b, bkt)
        t0 = time.monotonic()
        with span("serve.prefill", bucket=bkt, rows=self.b * bkt,
                  tokens=sum(len(t) for _, _, t, _ in rows),
                  rids=[r.rid for _, r, _, _ in rows]):
            logits, cache = self._bucket_prefill(
                self.params, batch=self._frontend_batch(toks_pad),
                cache=cache, lens=jnp.asarray(lens))
        with span("serve.sync"):
            logits = np.asarray(logits)      # sync: the wall we observe
        if self.cost is not None and bkt in self._timed_buckets:
            self.cost.observe_prefill(bkt, time.monotonic() - t0)
        self._timed_buckets.add(bkt)
        for j, (slot, req, toks, pages) in enumerate(rows):
            depth = len(toks) + self.extra
            with span("serve.insert", rid=req.rid, slot=slot, len=depth):
                self.kv.insert(slot, pages, cache["k"][:, j, :depth],
                               cache["v"][:, j, :depth])
            self._emit(req, self._sample_slot(slot, logits[j:j + 1], req))
            self.pos[slot] = depth
            self.active[slot] = req
        return not blocked

    # --------------------------- containment -----------------------------

    def _evict_slot(self, slot: int) -> None:
        """Quarantine a slot whose occupant produced non-finite values.
        Paged: free AND ZERO its pages — the next occupant's ``p @ V``
        contracts every cache row (masked rows at weight 0), and
        0 * NaN = NaN.  Legacy: zero the slot's dense cache region."""
        if self.paged:
            r = self.active[slot]
            pages = self.alloc.free_owner(id(r))
            self.kv.zero_pages(pages)
            self.kv.clear_slot(slot)
        else:
            self.cache = jax.tree.map(
                lambda leaf: leaf.at[:, slot].set(
                    jnp.zeros_like(leaf[:, slot])), self.cache)

    def _requarantine_prefill(self, slot: int, req: Request) -> None:
        """Re-prefill prompt + generated-so-far after quarantine, through
        whichever rung fits (bucket / exact-length)."""
        toks = self._req_tokens(req)
        if not self.paged:
            self._prefill_one(slot, req, tokens=toks)
            return
        self.active[slot] = None
        self.pos[slot] = 0
        bkt = bucket_for(len(toks), self.buckets)
        if bkt is None:
            self._admit_exact(slot, req, toks)
        else:
            self._admit_bucket([slot], [(req, toks)], bkt)

    def _expire_deadlines(self) -> None:
        now = time.monotonic()
        for slot, r in enumerate(self.active):
            if (r is not None and r.deadline_s is not None
                    and now - r.submitted_at > r.deadline_s):
                r.done = True
                r.timed_out = True
                self.faults["deadline_expired"] += 1
                self._release_slot(slot, r)
        kept = []
        for r in self.queue:
            if (r.deadline_s is not None
                    and now - r.submitted_at > r.deadline_s):
                r.done = True
                r.timed_out = True
                self.faults["deadline_expired"] += 1
            else:
                kept.append(r)
        self.queue = kept
        self._shed_infeasible(now)

    def _shed_infeasible(self, now: float) -> None:
        """Load shedding: drop queued requests whose deadline the current
        estimates say cannot be met, OLDEST first (they block everything
        behind them and are the most doomed).  Estimate-gated: nothing is
        shed until the cost model has measured wall times."""
        if self.cost is None or not self.cost.calibrated():
            return
        step = self.cost.step_s()
        ahead = sum(max(r.max_new_tokens - len(r.out_tokens), 0)
                    for r in self.active if r is not None)
        kept = []
        for r in self.queue:
            rem = max(r.max_new_tokens - len(r.out_tokens), 0)
            if r.deadline_s is None:
                kept.append(r)
                ahead += rem
                continue
            pre = self.cost.prefill_s(
                bucket_for(len(self._req_tokens(r)), self.buckets)) or 0.0
            est = ((now - r.submitted_at) + pre
                   + (ahead / self.b) * step + rem * step)
            if est > r.deadline_s:
                r.done = True
                r.timed_out = True
                r.shed = True
                self.faults["shed"] += 1
            else:
                kept.append(r)
                ahead += rem
        self.queue = kept

    def _decode_with_retry(self, last: np.ndarray, pos: jnp.ndarray):
        """Run one fused decode, retrying transient faults with exponential
        backoff (bounded; the last attempt propagates)."""
        for attempt in range(self.decode_retries + 1):
            try:
                _chaos.fire("transient_decode")
                with span("serve.decode",
                          active=sum(r is not None for r in self.active),
                          pages_used=(self.alloc.total - self.alloc.available
                                      if self.paged else 0)):
                    if self.paged:
                        return self._decode(
                            self.params, tokens=jnp.asarray(last),
                            cache=self.kv.cache(), pos=pos,
                            page_table=jnp.asarray(self.kv.table))
                    return self._decode(self.params,
                                        tokens=jnp.asarray(last),
                                        cache=self.cache, pos=pos)
            except _chaos.TransientFault:
                self.faults["transient_retries"] += 1
                if attempt == self.decode_retries:
                    raise
                time.sleep(self.retry_backoff_s * (2 ** attempt))

    def health(self) -> dict:
        """Operational snapshot: slot occupancy, fault counters, tokens
        chosen on the host and on the device, page-pool pressure,
        admission pricing, and the dispatch ladder's degraded-servings
        telemetry."""
        from ..core.gemm import plan_mode_stats
        degraded = plan_mode_stats().get("degraded", {})
        out = {
            "active_slots": sum(r is not None for r in self.active),
            "queue_depth": len(self.queue),
            "slot_pos": [int(p) for p in self.pos],
            "prefill_cache_size": len(self._prefill_cache),
            "faults": dict(self.faults),
            "sampling": dict(self.sampling),
            "degraded_servings": dict(degraded),
            "degraded_mode": bool(degraded)
                             or any(self.faults.values()),
        }
        if self.paged:
            out["pages"] = {"total": self.alloc.total,
                            "free": self.alloc.available,
                            "page_size": self.page_size,
                            "live_owners": self.alloc.live_owners}
            out["buckets"] = list(self.buckets)
            out["cost"] = self.cost.snapshot()
        if self._detok is not None:
            out["detok_backlog"] = self._detok.q.qsize()
        return out

    # ------------------------------ stepping -----------------------------

    def step(self) -> int:
        """One decode tick across all active slots; returns #active."""
        with span("serve.step",
                  active=sum(r is not None for r in self.active),
                  queue=len(self.queue)):
            return self._tick()

    def _tick(self) -> int:
        self._expire_deadlines()
        self._admit()
        if self.paged:
            self._ensure_pages()
        if not any(r is not None for r in self.active):
            return 0
        last = np.zeros((self.b, 1), np.int32)
        for i, r in enumerate(self.active):
            if r is not None and r.out_tokens:
                last[i, 0] = r.out_tokens[-1]
        # Single fused decode over all slots with PER-SLOT positions: each
        # row writes its own cache row and masks under its own horizon, so
        # mixed-depth slots (and freed-slot reuse) can't cross-contaminate.
        t0 = time.monotonic()
        logits, new_cache = self._decode_with_retry(
            last, jnp.asarray(self.pos))
        with span("serve.sync"):
            logits = np.asarray(logits)
        logits = _chaos.poison_logits(logits)
        if self.cost is not None and self._timed_step:
            self.cost.observe_step(time.monotonic() - t0)
        self._timed_step = True
        if self.paged:
            self.kv.update(new_cache)
        else:
            self.cache = new_cache
        finite = np.isfinite(logits).all(axis=-1)
        n_active = 0
        for i, r in enumerate(self.active):
            if r is None:
                continue
            if not finite[i]:
                # Quarantine: drop the slot's (possibly poisoned) cache and
                # re-prefill prompt + tokens generated so far — the request
                # continues instead of emitting garbage.
                self.faults["nonfinite_quarantined"] += 1
                self._evict_slot(i)
                self._requarantine_prefill(i, r)
                r = self.active[i]
                if r is None:       # re-prefill blocked on page pressure
                    continue
            else:
                self._emit(r, self._sample_slot(i, logits[i:i + 1], r))
                self.pos[i] += 1
            if (len(r.out_tokens) >= r.max_new_tokens
                    or self.pos[i] >= self.max_len - 1 + self.extra):
                r.done = True
                self._release_slot(i, r)
            else:
                n_active += 1
        return n_active

    def drain_detok(self) -> None:
        """Block until every emitted token has been detokenized."""
        if self._detok is not None:
            self._detok.drain()

    def close(self) -> None:
        if self._detok is not None:
            self._detok.close()
            self._detok = None

    def run(self, requests: list[Request]) -> list[Request]:
        for r in requests:
            self.submit(r)
        while self.queue or any(r is not None for r in self.active):
            self.step()
        self.drain_detok()
        return requests
