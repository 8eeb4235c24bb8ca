"""Serving engine: batched continuous decoding matches single-request decode,
tokens are chosen from the host's logits as the device would choose them,
and the overload-safety machinery (admission, shedding, preemption, the
bucket-miss rung, off-loop detokenization) behaves under pressure."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import init_params
from repro.runtime import chaos
from repro.serve.engine import Overloaded, Request, ServeEngine


def test_engine_greedy_matches_single():
    cfg = get_config("qwen3-1.7b-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(3)]

    def run(reqs, slots):
        eng = ServeEngine(cfg, params, batch_slots=slots, max_len=32)
        return eng.run([Request(rid=i, prompt=p, max_new_tokens=5)
                        for i, p in enumerate(reqs)])

    single = [run([p], slots=1)[0].out_tokens for p in prompts]
    batched = [r.out_tokens for r in run(prompts, slots=3)]
    for s, b in zip(single, batched):
        assert s == b, (s, b)


def test_engine_slot_reuse_mixed_lengths():
    """Regression: freed-slot reuse with MIXED prompt lengths / depths.
    The fused decode used to run every slot at ``max(pos)`` — the shallower
    slot wrote the wrong KV row and masked under the deeper slot's horizon,
    so a short request sharing a batch with a long one diverged from its
    solo decode.  Per-slot position vectors fix it; this pins the fix."""
    cfg = get_config("qwen3-1.7b-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    lens, mnts = [5, 12, 9, 7], [3, 10, 6, 8]
    prompts = [rng.integers(2, cfg.vocab_size, s).astype(np.int32)
               for s in lens]

    def run(reqs, slots):
        eng = ServeEngine(cfg, params, batch_slots=slots, max_len=48)
        return eng.run(reqs)

    single = [run([Request(rid=0, prompt=p, max_new_tokens=m)],
                  slots=1)[0].out_tokens
              for p, m in zip(prompts, mnts)]
    batched = run([Request(rid=i, prompt=p, max_new_tokens=m)
                   for i, (p, m) in enumerate(zip(prompts, mnts))], slots=2)
    for r, ref in zip(batched, single):
        assert r.out_tokens == ref, (r.rid, r.out_tokens, ref)


def test_engine_queues_beyond_slots():
    cfg = get_config("mamba2-370m-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(2, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=4) for i in range(5)]
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=24)
    done = eng.run(reqs)
    assert all(len(r.out_tokens) == 4 for r in done)


# ----------------------- overload-safety machinery -------------------------

def _bits(seed=0):
    cfg = get_config("qwen3-1.7b-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    return cfg, params, rng


def test_page_exhaustion_preempts_and_recovers_bit_identical():
    """Forced page exhaustion at a decode-growth allocation preempts the
    lowest-priority (youngest) victim; after re-queue + re-prefill of
    prompt + generated-so-far, BOTH requests finish with exactly the
    tokens of the undisturbed run (greedy decode)."""
    cfg, params, rng = _bits(5)
    prompts = [rng.integers(2, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(2)]
    mk = lambda: [Request(rid=i, prompt=p, max_new_tokens=8)
                  for i, p in enumerate(prompts)]
    ref = [r.out_tokens for r in ServeEngine(
        cfg, params, batch_slots=2, max_len=32, page_size=4).run(mk())]
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, page_size=4)
    # occurrences 0/1 are the two admission allocs (never preempt); 2 is
    # the first decode-growth alloc -> the preemption path.
    with chaos.chaos(chaos.FaultPlan(
            [chaos.Fault("page_exhaustion", at=2)])):
        out = eng.run(mk())
    assert [r.out_tokens for r in out] == ref
    assert eng.faults["preemptions"] == 1
    assert eng.health()["degraded_mode"]
    eng.alloc.check()
    assert eng.alloc.available == eng.alloc.total   # drained clean


def test_bucket_miss_falls_back_to_exact_prefill():
    cfg, params, rng = _bits(6)
    prompt = rng.integers(2, cfg.vocab_size, 9).astype(np.int32)
    mk = lambda: [Request(rid=0, prompt=prompt, max_new_tokens=4)]
    ref = ServeEngine(cfg, params, batch_slots=1,
                      max_len=32).run(mk())[0].out_tokens
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=32)
    with chaos.chaos(chaos.FaultPlan([chaos.Fault("bucket_miss", at=0)])):
        out = eng.run(mk())[0].out_tokens
    assert out == ref
    assert eng.faults["bucket_misses"] == 1
    assert len(eng._prefill_cache) == 1     # the legacy rung compiled


def test_admission_rejects_with_typed_overloaded():
    """Once the cost model is calibrated, a deadline the projected
    completion cannot meet is rejected at submit() — typed, immediate,
    nothing queued.  Uncalibrated engines admit unconditionally."""
    cfg, params, rng = _bits(7)
    prompt = rng.integers(2, cfg.vocab_size, 6).astype(np.int32)
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=64)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=4,
                       deadline_s=1e-9))   # uncalibrated: admitted
    eng.queue.clear()
    # Two calibration requests: the first prefill/step walls per compiled
    # shape are compile time and deliberately not fed to the cost model.
    eng.run([Request(rid=1, prompt=prompt, max_new_tokens=4),
             Request(rid=11, prompt=prompt, max_new_tokens=4)])
    assert eng.cost.calibrated()
    with pytest.raises(Overloaded) as ei:
        eng.submit(Request(rid=2, prompt=prompt, max_new_tokens=40,
                           deadline_s=1e-9))
    assert ei.value.projected_s is not None
    assert ei.value.projected_s > ei.value.deadline_s
    assert eng.faults["admission_rejected"] == 1
    assert eng.queue == []                 # rejected, not queued


def test_oversized_request_rejected_up_front():
    cfg, params, rng = _bits(8)
    prompt = rng.integers(2, cfg.vocab_size, 6).astype(np.int32)
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=64,
                      page_size=4, num_pages=2)   # pool: 8 KV rows
    with pytest.raises(Overloaded, match="KV pages"):
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=60))


def test_shedding_drops_infeasible_queued_work_oldest_first():
    cfg, params, rng = _bits(9)
    prompt = rng.integers(2, cfg.vocab_size, 6).astype(np.int32)
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=64)
    # Two calibration requests (first walls per shape are compile time and
    # skipped); shedding is estimate-gated so it needs a calibrated model.
    eng.run([Request(rid=0, prompt=prompt, max_new_tokens=4),
             Request(rid=10, prompt=prompt, max_new_tokens=4)])
    # Hand-queue around submit(): two deadline-infeasible requests and one
    # feasible one behind them — the infeasible pair sheds, the feasible
    # survives and completes.
    # Deadlines NOT yet expired (2s out) but infeasible: 100k tokens of
    # remaining work prices far beyond 2s at any measured step time.
    now = time.monotonic()
    doomed = [Request(rid=1, prompt=prompt, max_new_tokens=100_000,
                      deadline_s=2.0),
              Request(rid=2, prompt=prompt, max_new_tokens=100_000,
                      deadline_s=2.0)]
    ok = Request(rid=3, prompt=prompt, max_new_tokens=2, deadline_s=60.0)
    for r in doomed + [ok]:
        r.submitted_at = now
        eng.queue.append(r)
    while eng.queue or any(a is not None for a in eng.active):
        eng.step()
    assert all(r.shed and r.done for r in doomed)
    assert eng.faults["shed"] == 2
    assert not ok.shed and len(ok.out_tokens) == 2


def test_detokenize_runs_off_the_decode_loop():
    """slow_step-style timing proof: a deliberately slow detokenizer must
    not stall the decode loop — the worker thread absorbs it, and drain()
    delivers the complete text afterwards."""
    cfg, params, rng = _bits(10)
    prompt = rng.integers(2, cfg.vocab_size, 6).astype(np.int32)
    per_tok = 0.05
    slow = lambda t: (time.sleep(per_tok), f"<{t}>")[1]
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=32,
                      detokenize=slow)
    eng.run([Request(rid=0, prompt=prompt, max_new_tokens=3)])  # warm/compile
    req = Request(rid=1, prompt=prompt, max_new_tokens=9)
    eng.submit(req)
    t0 = time.monotonic()
    while eng.queue or any(a is not None for a in eng.active):
        eng.step()
    loop_wall = time.monotonic() - t0
    total_sleep = per_tok * (req.max_new_tokens + 1)
    assert loop_wall < total_sleep * 0.8, (loop_wall, total_sleep)
    eng.drain_detok()
    assert req.text == "".join(f"<{t}>" for t in req.out_tokens)
    eng.close()


def test_priority_protects_high_priority_from_preemption():
    """Under forced exhaustion the LOWER-priority active request is the
    victim, even when it is older."""
    cfg, params, rng = _bits(11)
    prompts = [rng.integers(2, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(2)]
    lo = Request(rid=0, prompt=prompts[0], max_new_tokens=8, priority=0)
    hi = Request(rid=1, prompt=prompts[1], max_new_tokens=8, priority=5)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, page_size=4)
    # occ 0/1: admission allocs; occ 2: lo's growth (succeeds untouched);
    # occ 3: HI's growth forced-exhausted -> victim must be lo (priority 0)
    # even though lo is the older request.
    with chaos.chaos(chaos.FaultPlan(
            [chaos.Fault("page_exhaustion", at=3)])):
        eng.run([lo, hi])
    assert eng.faults["preemptions"] == 1
    assert len(lo.out_tokens) == 8 and len(hi.out_tokens) == 8


# ------------------------------ sampling --------------------------------

def _device_argmax(self, logits, req):
    """Greedy as the engine chose it before host sampling: upload the row,
    argmax on the device, read the index back."""
    return int(np.asarray(jnp.argmax(jnp.asarray(logits), -1))[0])


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("tied", [(0, 4095), (9, 10, 2048), ()],
                         ids=["first-last", "inner", "constant"])
def test_host_greedy_breaks_ties_as_the_device(dtype, tied):
    """Equal maxima resolve to the lowest index on the host, as
    ``jnp.argmax`` resolves them; ``()`` is a row of one value.  The
    greedy branch reads nothing of the engine, so no engine is built."""
    rng = np.random.default_rng(len(tied))
    row = (rng.standard_normal((1, 4096)) if tied
           else np.full((1, 4096), 0.5)).astype(dtype)
    if tied:
        row[0, list(tied)] = row.max() + 1
    req = Request(rid=0, prompt=np.zeros(1, np.int32))
    host = ServeEngine._sample(None, row, req)
    assert host == _device_argmax(None, row, req) == (tied or (0,))[0]


@pytest.mark.parametrize("arch", ["qwen3-1.7b-smoke", "mamba2-370m-smoke"])
def test_host_greedy_serves_the_device_greedy_tokens(arch, monkeypatch):
    """A batched run (bucketed prefill and decode for the paged family,
    exact-length prefill for the recurrent one) emits the same tokens as
    one whose every token goes through the device argmax."""
    cfg = get_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 8)]

    def run():
        eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
        return [r.out_tokens for r in eng.run(
            [Request(rid=i, prompt=p, max_new_tokens=6)
             for i, p in enumerate(prompts)])]

    host = run()
    monkeypatch.setattr(ServeEngine, "_sample", _device_argmax)
    assert run() == host


def test_temperature_draws_follow_the_engine_key():
    """Temperature rows draw with ``jax.random.categorical`` on the
    engine's key, split once per such token in emission order; greedy rows
    in the same batch split nothing."""
    cfg, params, rng = _bits(12)
    prompts = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 9, 7)]
    eng = ServeEngine(cfg, params, batch_slots=3, max_len=32, seed=41)
    seen = []
    sample = eng._sample

    def recorded(logits, req):
        tok = sample(logits, req)
        seen.append((np.array(logits), req.temperature, tok))
        return tok

    eng._sample = recorded
    # Temperatures low enough that the scaled logits, not the Gumbel
    # noise alone, decide the draw.
    eng.run([Request(rid=i, prompt=p, max_new_tokens=8, temperature=t)
             for i, (p, t) in enumerate(zip(prompts, (0.1, 0.0, 0.25)))])
    key = jax.random.PRNGKey(41)
    drawn = off_argmax = 0
    for row, t, tok in seen:
        if t <= 0:
            assert tok == int(np.argmax(row[0]))
            continue
        key, sub = jax.random.split(key)
        ref = jax.random.categorical(sub, jnp.asarray(row) / t, axis=-1)
        assert tok == int(np.asarray(ref)[0])
        drawn += 1
        off_argmax += tok != int(np.argmax(row[0]))
    assert drawn == 16 and len(seen) == 24
    assert off_argmax > 0       # the draws are not greedy in disguise


@pytest.mark.parametrize("temperature,on", [(0.0, "host"), (0.9, "device")])
def test_sampling_counter_counts_every_token(temperature, on):
    cfg, params, rng = _bits(13)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    reqs = eng.run([Request(rid=i, prompt=rng.integers(
        2, cfg.vocab_size, n).astype(np.int32), max_new_tokens=m,
        temperature=temperature) for i, (n, m) in enumerate(
            ((5, 4), (9, 7), (6, 3)))])
    emitted = sum(len(r.out_tokens) for r in reqs)
    assert emitted == 14
    other = "device" if on == "host" else "host"
    assert eng.health()["sampling"] == {on: emitted, other: 0}
