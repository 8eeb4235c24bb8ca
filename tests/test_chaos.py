"""Chaos-tested graceful degradation: seeded fault injection, the dispatch
fallback ladder (every rung oracle-checked, degraded counter exactly once
per fault), elastic re-planned recovery, plan-store crash/corruption
atomicity, and serve-engine containment."""
import json
import warnings

import numpy as np
import pytest
from helpers import run_with_devices

import jax
import jax.numpy as jnp

from repro.core.gemm import (batched_matmul, matmul, plan_mode_stats,
                             ragged_matmul, ragged_swiglu)
from repro.core.gemm import dispatch as _dispatch
from repro.core.gemm import plan_store
from repro.core.gemm.tuner import clear_plan_cache, clear_planner_caches
from repro.runtime import chaos


# ----------------------------- the harness --------------------------------

def test_fault_plan_occurrence_windows():
    p = chaos.FaultPlan([chaos.Fault("kernel", at=1, count=2)])
    fired = [p.should_fire("kernel") is not None for _ in range(5)]
    assert fired == [False, True, True, False, False]
    assert p.counters["kernel"] == 5 and p.fired["kernel"] == 2
    # other sites are independent
    assert p.should_fire("ep_ring") is None


def test_parse_env_spec():
    p = chaos.parse_env(
        "kernel@2x3; shard_loss@1:chips=4 ;slow_step@0:delay_s=0.5;seed=7")
    assert p.seed == 7
    k = [f for f in p.faults if f.site == "kernel"][0]
    assert (k.at, k.count) == (2, 3)
    s = [f for f in p.faults if f.site == "shard_loss"][0]
    assert s.chips == 4
    d = [f for f in p.faults if f.site == "slow_step"][0]
    assert d.delay_s == 0.5


@pytest.mark.parametrize("spec,needle", [
    ("kernel@0;", "trailing ';'"),
    ("kernel@0;;slow_step@1", "empty segment"),
    ("kernle@0", "unknown site 'kernle'"),
    ("kernel@x", "occurrence 'x' is not an integer"),
    ("kernel@0x1.5", "count '1.5' is not an integer"),
    ("shard_loss@0:chps=4", "unknown payload key 'chps'"),
    ("slow_step@0:delay_s=fast", "payload delay_s='fast' is not numeric"),
    ("shard_loss@0:chips", "'chips' is not key=value"),
    ("seed=pi", "seed must be an integer"),
])
def test_parse_env_rejects_malformed_specs(spec, needle):
    """A typo'd REPRO_CHAOS must fail loudly at startup, naming the
    offending segment — a chaos CI leg that silently arms nothing would
    pass while testing nothing."""
    with pytest.raises(ValueError) as ei:
        chaos.parse_env(spec)
    msg = str(ei.value)
    assert "malformed REPRO_CHAOS segment" in msg
    assert needle in msg, (needle, msg)


def test_parse_env_empty_spec_is_no_plan():
    assert chaos.parse_env("").faults == []
    assert chaos.parse_env("   ").faults == []


def test_parse_env_new_sites_and_burst_payload():
    p = chaos.parse_env(
        "page_exhaustion@2;bucket_miss@0x3;burst_arrival@1:burst=8")
    sites = {f.site: f for f in p.faults}
    assert sites["page_exhaustion"].at == 2
    assert sites["bucket_miss"].count == 3
    assert sites["burst_arrival"].burst == 8


def test_clear_plan_cache_resets_degraded_and_warn_once_state():
    """Regression: ``clear_plan_cache`` is documented as THE single reset
    entry point, but the dispatch ladder's warn-once dedup set used to
    survive it — after a reset, a recurring degradation was silently
    swallowed instead of logged again."""
    from repro.core.gemm import dispatch, tuner
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        dispatch._degraded("dense", "pallas->xla", RuntimeError("boom"))
    assert tuner.DEGRADED_COUNTS
    assert dispatch._WARNED_RUNGS
    tuner.clear_plan_cache()
    assert not tuner.DEGRADED_COUNTS
    assert not dispatch._WARNED_RUNGS


def test_context_manager_restores_state():
    with chaos.chaos(chaos.FaultPlan([chaos.Fault("kernel")])):
        assert chaos.active() is not None
        with pytest.raises(chaos.KernelLaunchFailure):
            chaos.fire("kernel")
    assert chaos.should_fire("kernel") is None   # no plan outside the block


# ------------------------- dispatch fallback ladder ------------------------

def _degraded_counts() -> dict:
    return dict(plan_mode_stats().get("degraded", {}))


def _rng(seed, *shape):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


def test_ladder_dense_pallas_to_xla():
    a, b = _rng(0, 24, 16), _rng(1, 16, 20)
    oracle = matmul(a, b, backend="xla")
    before = _degraded_counts().get("dense:pallas->xla", 0)
    with chaos.chaos(chaos.FaultPlan([chaos.Fault("kernel", at=0)])):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            out = matmul(a, b, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)
    assert _degraded_counts()["dense:pallas->xla"] == before + 1


def test_ladder_batched_pallas_to_xla():
    a, b = _rng(2, 3, 24, 16), _rng(3, 3, 16, 20)
    oracle = batched_matmul(a, b, backend="xla")
    before = _degraded_counts().get("batched:pallas->xla", 0)
    with chaos.chaos(chaos.FaultPlan([chaos.Fault("kernel", at=0)])):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            out = batched_matmul(a, b, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)
    assert _degraded_counts()["batched:pallas->xla"] == before + 1


def test_ladder_ragged_pallas_to_xla():
    x, w = _rng(4, 24, 16), _rng(5, 2, 16, 20)
    offs = jnp.asarray([0, 10, 24], jnp.int32)
    oracle = ragged_matmul(x, w, offs, backend="xla")
    before = _degraded_counts().get("ragged:pallas->xla", 0)
    with chaos.chaos(chaos.FaultPlan([chaos.Fault("kernel", at=0)])):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            out = ragged_matmul(x, w, offs, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)
    assert _degraded_counts()["ragged:pallas->xla"] == before + 1


def test_ladder_fused_to_unfused_swiglu():
    x = _rng(6, 24, 16)
    wg, wu = _rng(7, 2, 16, 20), _rng(8, 2, 16, 20)
    offs = jnp.asarray([0, 10, 24], jnp.int32)
    oracle = ragged_swiglu(x, wg, wu, offs, backend="xla")
    before = _degraded_counts().get("ragged:fused->unfused", 0)
    with chaos.chaos(chaos.FaultPlan([chaos.Fault("kernel_fused", at=0)])):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            out = ragged_swiglu(x, wg, wu, offs, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)
    assert _degraded_counts()["ragged:fused->unfused"] == before + 1


def test_ladder_counts_once_per_fault_and_warns_once():
    a, b = _rng(9, 24, 16), _rng(10, 16, 20)
    _dispatch._WARNED_RUNGS.discard(("dense", "pallas->xla"))
    before = _degraded_counts().get("dense:pallas->xla", 0)
    with chaos.chaos(chaos.FaultPlan([chaos.Fault("kernel", at=0, count=2)])):
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            matmul(a, b, backend="pallas_interpret")
            matmul(a[:23], b, backend="pallas_interpret")  # new shape: retrace
    assert _degraded_counts()["dense:pallas->xla"] == before + 2
    ladder = [r for r in rec if "gemm dispatch degraded" in str(r.message)]
    assert len(ladder) == 1    # first occurrence logged, repeats silent


def _boom(*_args, **_kwargs):
    raise RuntimeError("Mosaic refused the kernel")


@pytest.mark.parametrize("kernel,call", [
    ("gemm", lambda: matmul(_rng(11, 40, 16), _rng(12, 16, 20),
                            backend="pallas_interpret")),
    ("batched_gemm", lambda: batched_matmul(
        _rng(13, 3, 40, 16), _rng(14, 3, 16, 20),
        backend="pallas_interpret")),
    ("ragged_gemm", lambda: ragged_matmul(
        _rng(15, 40, 16), _rng(16, 2, 16, 20),
        jnp.asarray([0, 17, 40], jnp.int32), backend="pallas_interpret")),
    ("ragged_gemm_swiglu", lambda: ragged_swiglu(
        _rng(17, 40, 16), _rng(18, 2, 16, 20), _rng(19, 2, 16, 20),
        jnp.asarray([0, 17, 40], jnp.int32), backend="pallas_interpret")),
])
def test_ladder_real_kernel_error_propagates(monkeypatch, kernel, call):
    """Only injected faults take the ladder: a real kernel error (a Mosaic
    refusal, a lowering bug) reaches the caller, and nothing is counted as
    a degraded serving."""
    clear_plan_cache()
    monkeypatch.setattr(_dispatch._ops, kernel, _boom)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        call()
    assert "degraded" not in plan_mode_stats()


def test_ep_ladder_real_error_propagates():
    from repro.core.gemm import distributed
    clear_plan_cache()

    def run(schedule):
        raise ValueError(f"{schedule} executor failed to lower")

    with pytest.raises(ValueError, match="ring executor"):
        distributed._ep_ladder(run, "ring", lambda: None)
    assert "degraded" not in plan_mode_stats()


# -------------------- stale-shard plans after a re-mesh --------------------

def test_stale_shard_cached_plans_not_served():
    """Placed plans are keyed with a ``|shards{n}`` suffix: a measured
    winner recorded at 8 shards must not be served when the elastic shrink
    re-plans at 4."""
    from repro.core.gemm.tuner import plan_gemm
    clear_plan_cache()
    try:
        p8 = plan_gemm(4096, 1024, 2048, num_shards=8, axis="data")
        store = plan_store.get_store()
        store.put(
            plan_store.shape_key("dense", (4096, 1024, 2048), 4, 4,
                                 num_shards=8),
            {"bm": p8.bm, "bn": p8.bn, "bk": p8.bk,
             "dim_order": p8.dim_order,
             "strategy": p8.placement.strategy,
             "schedule": p8.placement.schedule,
             "mode": "measured"})
        clear_planner_caches()
        assert plan_gemm(4096, 1024, 2048,
                         num_shards=8, axis="data").mode == "cached"
        p4 = plan_gemm(4096, 1024, 2048, num_shards=4, axis="data")
        assert p4.mode == "analytic"
        assert p4.placement.num_shards == 4
    finally:
        clear_plan_cache()


# ---------------------- plan-store crash & corruption ----------------------

def test_crash_mid_save_leaves_store_intact(tmp_path):
    path = str(tmp_path / "plans.json")
    st = plan_store.PlanStore()
    st.put("dense|64x64x64|ib4|ob4", {"bm": 64, "bn": 64, "bk": 64})
    st.save(path)
    st.put("dense|128x64x64|ib4|ob4", {"bm": 128, "bn": 64, "bk": 64})
    with chaos.chaos(chaos.FaultPlan([chaos.Fault("plan_save_crash")])):
        with pytest.raises(chaos.ChaosError):
            st.save(path)
    # the crash hit between temp-write and rename: the original file is
    # byte-for-byte valid JSON with the OLD contents, and no temp litter
    blob = json.loads(open(path).read())
    assert list(blob["entries"]) == ["dense|64x64x64|ib4|ob4"]
    assert not [p for p in tmp_path.iterdir()
                if p.name.startswith(".plan_cache.")]
    # the next (un-faulted) save succeeds and lands both entries
    st.save(path)
    assert len(json.loads(open(path).read())["entries"]) == 2


@pytest.mark.parametrize("mode", ["truncate", "scramble"])
def test_corrupt_plan_cache_degrades_gracefully(tmp_path, mode):
    path = str(tmp_path / "plans.json")
    st = plan_store.PlanStore()
    st.put("dense|64x64x64|ib4|ob4", {"bm": 64, "bn": 64, "bk": 64})
    st.save(path)
    chaos.corrupt_json(path, seed=3, mode=mode)
    fresh = plan_store.PlanStore()
    n = fresh.load(path)         # never raises, whatever the damage
    assert n == 0 and fresh.entries == {}
    assert fresh.lookup("dense|64x64x64|ib4|ob4") is None


def test_corrupt_json_deterministic(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    for p in (a, b):
        p.write_text(json.dumps({"k": list(range(64))}))
        chaos.corrupt_json(str(p), seed=11, mode="truncate")
    assert a.read_bytes() == b.read_bytes()


# -------------------------- serve-engine containment -----------------------

def _serve_bits():
    from repro.configs import get_config
    from repro.models.model import init_params
    from repro.serve.engine import Request, ServeEngine
    cfg = get_config("qwen3-1.7b-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(
        2, cfg.vocab_size, 6).astype(np.int32)
    return cfg, params, prompt, Request, ServeEngine


def test_serve_transient_retry_is_transparent():
    cfg, params, prompt, Request, ServeEngine = _serve_bits()
    ref = ServeEngine(cfg, params, batch_slots=2, max_len=32).run(
        [Request(rid=0, prompt=prompt, max_new_tokens=4)])[0].out_tokens
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    with chaos.chaos(chaos.FaultPlan(
            [chaos.Fault("transient_decode", at=1)])):
        out = eng.run([Request(rid=0, prompt=prompt,
                               max_new_tokens=4)])[0].out_tokens
    assert out == ref
    assert eng.faults["transient_retries"] == 1
    assert eng.health()["degraded_mode"]


def test_serve_nan_quarantine_reprefills():
    cfg, params, prompt, Request, ServeEngine = _serve_bits()
    ref = ServeEngine(cfg, params, batch_slots=2, max_len=32).run(
        [Request(rid=0, prompt=prompt, max_new_tokens=4)])[0].out_tokens
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    with chaos.chaos(chaos.FaultPlan(
            [chaos.Fault("nan_logits", at=1, slot=0)])):
        req = eng.run([Request(rid=0, prompt=prompt,
                               max_new_tokens=4)])[0]
    assert req.out_tokens == ref          # no garbage token emitted
    assert all(t >= 0 for t in req.out_tokens)
    assert eng.faults["nonfinite_quarantined"] == 1


def test_serve_deadline_expires_and_frees_slot():
    cfg, params, prompt, Request, ServeEngine = _serve_bits()
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=32)
    doomed = Request(rid=0, prompt=prompt, max_new_tokens=10_000,
                     deadline_s=0.0)
    ok = Request(rid=1, prompt=prompt, max_new_tokens=2)
    out = eng.run([doomed, ok])
    assert out[0].timed_out and out[0].done
    assert len(out[1].out_tokens) == 2 and not out[1].timed_out
    assert eng.faults["deadline_expired"] == 1


def test_serve_prefill_cache_lru_bounded():
    # paged=False pins the legacy exact-length rung: bucketed prefill
    # would fold all four lengths into one compiled bucket (no LRU churn).
    cfg, params, _, Request, ServeEngine = _serve_bits()
    rng = np.random.default_rng(1)
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=48,
                      prefill_cache_size=2, paged=False)
    eng.run([Request(rid=i,
                     prompt=rng.integers(2, cfg.vocab_size,
                                         4 + i).astype(np.int32),
                     max_new_tokens=1) for i in range(4)])
    h = eng.health()
    assert h["prefill_cache_size"] <= 2
    assert h["faults"]["prefill_evictions"] == 2


# ------------------------ trainer failure semantics ------------------------

def test_trainer_no_final_checkpoint_on_failure(tmp_path):
    """A mid-run HostFailure must NOT leave a checkpoint labelled with the
    final step — the elastic restart would resume past steps that never
    ran."""
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.optim.adamw import OptConfig
    from repro.runtime.fault_tolerance import HostFailure
    from repro.train.trainer import Trainer
    cfg = get_config("qwen3-1.7b-smoke")
    shape = ShapeConfig("t", seq_len=16, global_batch=2, kind="train")
    tr = Trainer(cfg, shape, OptConfig(lr=1e-3, total_steps=8),
                 ckpt_dir=str(tmp_path), ckpt_every=2, log_every=100)
    with chaos.chaos(chaos.FaultPlan(
            [chaos.Fault("shard_loss", at=5, chips=2)])):
        with pytest.raises(HostFailure):
            tr.run(8)
    tr.ckpt.wait()                 # join the async periodic writer
    latest = tr.ckpt.latest_step()
    assert latest == 4             # periodic saves only, never step 7


# ----------------------- multi-device (subprocess) legs --------------------

@pytest.mark.slow
def test_ep_ladder_multidevice():
    """Every EP rung (ring->gather, gather->single, and the full ladder)
    under injected collective faults on an 8-shard mesh: numerically equal
    to the healthy run, degraded counter exactly once per fault."""
    run_with_devices("""
import numpy as np
import jax.numpy as jnp
from repro.core.gemm import ep_ragged_matmul, ep_ragged_moe, plan_mode_stats
from repro.launch.mesh import make_mesh
from repro.runtime import chaos

mesh = make_mesh((8,), ("data",))
rs = np.random.RandomState(0)
x = jnp.asarray(rs.randn(64, 16), jnp.float32)
w = jnp.asarray(rs.randn(8, 16, 24), jnp.float32)
offs = jnp.asarray(np.linspace(0, 64, 9, dtype=np.int32))
ref = np.concatenate([np.asarray(x)[offs[g]:offs[g+1]] @ np.asarray(w)[g]
                      for g in range(8)])

def deg():
    return dict(plan_mode_stats().get("degraded", {}))

with chaos.chaos(chaos.FaultPlan([chaos.Fault("ep_ring", at=0)])):
    y = ep_ragged_matmul(x, w, offs, mesh=mesh, schedule="ring")
np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)
assert deg()["ep:ring->gather"] == 1, deg()

with chaos.chaos(chaos.FaultPlan([chaos.Fault("ep_gather", at=0)])):
    y = ep_ragged_matmul(x, w, offs, mesh=mesh, schedule="gather")
np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)
assert deg()["ep:gather->single"] == 1, deg()

with chaos.chaos(chaos.FaultPlan([chaos.Fault("ep_ring", at=0),
                                  chaos.Fault("ep_gather", at=0)])):
    y = ep_ragged_matmul(x, w, offs, mesh=mesh, schedule="ring")
np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)
assert deg() == {"ep:ring->gather": 2, "ep:gather->single": 2}, deg()

wg = jnp.asarray(rs.randn(8, 16, 24), jnp.float32)
wu = jnp.asarray(rs.randn(8, 16, 24), jnp.float32)
wd = jnp.asarray(rs.randn(8, 24, 16), jnp.float32)
healthy = ep_ragged_moe(x, wg, wu, wd, offs, mesh=mesh, schedule="gather")
with chaos.chaos(chaos.FaultPlan([chaos.Fault("ep_gather", at=0)])):
    m = ep_ragged_moe(x, wg, wu, wd, offs, mesh=mesh, schedule="gather")
np.testing.assert_allclose(np.asarray(m), np.asarray(healthy),
                           rtol=1e-4, atol=1e-4)
assert deg()["ep:gather->single"] == 3, deg()
print("OK")
""", n_devices=8, timeout=560)


@pytest.mark.slow
def test_elastic_replan_recovery_deterministic():
    """The acceptance-criterion test: an injected single-shard loss mid-run
    re-meshes via ElasticPlan, invalidates the executor caches (re-planning
    every placed GEMM on the new mesh — visible as fresh plan servings in
    plan_mode_stats), restores the checkpoint onto the shrunken mesh, and
    replays data deterministically: the post-recovery loss trajectory
    matches the same seed run WITHOUT the fault, and two identical faulted
    runs are bitwise identical."""
    run_with_devices("""
import tempfile
import numpy as np
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.gemm.tuner import PLAN_MODE_COUNTS, clear_plan_cache
from repro.optim.adamw import OptConfig
from repro.runtime import chaos
from repro.runtime.elastic import ElasticRunner

cfg = get_config("qwen3-1.7b-smoke")
shape = ShapeConfig("elastic", seq_len=32, global_batch=8, kind="train")
opt = OptConfig(lr=1e-3, warmup_steps=2, total_steps=12)

def run(fault):
    clear_plan_cache()
    r = ElasticRunner(cfg, shape, opt, ckpt_dir=tempfile.mkdtemp(),
                      model_parallel=1, seed=0, ckpt_every=4, log_every=1)
    plan = (chaos.FaultPlan([chaos.Fault("shard_loss", at=6, chips=2)])
            if fault else chaos.FaultPlan())
    with chaos.chaos(plan):
        r.run(12)
    return r, sum(PLAN_MODE_COUNTS.values())

clean, plans_clean = run(False)
faulted, plans_faulted = run(True)

assert len(clean.history) == 1
assert [h.get("failure") for h in faulted.history] == \
    [None, "HostFailure", None]
assert faulted.history[0]["mesh"] == (8, 1)
assert faulted.history[2]["mesh"] == (4, 1)        # 6 survivors -> dp 4
assert faulted.history[2]["start"] == 5            # ckpt_every=4 -> step 4
# the shrink re-planned the placed GEMMs: a second trace's worth of plan
# servings on top of the clean run's single trace
assert plans_faulted > plans_clean, (plans_faulted, plans_clean)

ref = {m["step"]: m["loss"] for m in clean.metrics_log}
got = {m["step"]: m["loss"] for m in faulted.metrics_log}
post = sorted(s for s in got if s >= 6)
assert post == list(range(6, 12))
for s in post:   # identical trajectory modulo mesh-shape reduction order
    assert abs(ref[s] - got[s]) < 5e-3, (s, ref[s], got[s])

faulted2, _ = run(True)
got2 = {m["step"]: m["loss"] for m in faulted2.metrics_log}
assert got == got2      # replay is exactly deterministic
print("OK")
""", n_devices=8, timeout=560)


@pytest.mark.slow
def test_chaos_ep_train_step_and_serve_smoke():
    """The CI chaos leg: a seeded FaultPlan driven through the 8-device EP
    train step (collective fault -> single-device rung inside the jitted
    step) and a serve loop (transient + NaN faults) — everything degrades,
    nothing crashes, telemetry records each fault."""
    run_with_devices("""
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.dist import DistContext, use_dist
from repro.core.gemm import plan_mode_stats
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.launch.sharding import batch_specs, expert_axis, param_specs, to_shardings
from repro.models.model import init_params
from repro.optim.adamw import OptConfig, init_opt_state
from repro.runtime import chaos
from repro.serve.engine import Request, ServeEngine
from repro.train.train_step import make_train_step

cfg = get_config("llama4-scout-17b-a16e-smoke")
shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
mesh = make_mesh((2, 4), ("data", "model"))
ctx = DistContext(mesh=mesh, dp_axes=("data",), model_axis="model",
                  moe_ep_axis=expert_axis(mesh, True, "dp"))
plan = chaos.FaultPlan([chaos.Fault("ep_ring", at=0),
                        chaos.Fault("ep_gather", at=0),
                        chaos.Fault("transient_decode", at=1),
                        chaos.Fault("nan_logits", at=2, slot=0)], seed=0)
with chaos.chaos(plan), use_dist(ctx), mesh:
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    ps = to_shardings(param_specs(params, mesh, moe_ep=True), mesh)
    os_ = to_shardings(param_specs(opt, mesh, zero_stage=3, moe_ep=True), mesh)
    ds = SyntheticLM(cfg, shape)
    batch = {k: jnp.asarray(v) for k, v in ds.host_batch(0).items()}
    bs = to_shardings(batch_specs(cfg, batch, mesh), mesh)
    step = jax.jit(make_train_step(cfg, OptConfig()),
                   in_shardings=(ps, os_, bs), donate_argnums=(0, 1))
    params, opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    deg = plan_mode_stats().get("degraded", {})
    assert deg.get("ep:gather->single", 0) >= 1, deg

    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48)
    rng = np.random.default_rng(0)
    reqs = eng.run([Request(rid=i,
                            prompt=rng.integers(2, cfg.vocab_size, 8).astype(np.int32),
                            max_new_tokens=4) for i in range(2)])
    assert all(len(r.out_tokens) == 4 for r in reqs)
    h = eng.health()
    assert h["faults"]["transient_retries"] == 1, h
    assert h["faults"]["nonfinite_quarantined"] == 1, h
    assert h["degraded_mode"]
print("OK")
""", n_devices=8, timeout=560)
