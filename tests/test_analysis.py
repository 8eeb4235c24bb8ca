"""Static kernel-contract verifier: mutation tests + shipped-candidate proof.

Every deliberately corrupted plan/BlockSpec/visit-list must be FLAGGED, and
every plan the shipped generators produce must PASS — plus the load-time
quarantine, the ``REPRO_VERIFY=1`` dispatch mode, the ragged zero-copy edge
path, and the committed-plan-cache round-trip (candidate pruning changes no
chosen plan)."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.analysis import contracts
from repro.analysis.sweep import run_sweep
from repro.core.gemm import dispatch, plan_store, tuner
from repro.core.gemm.cmr import TPU_V5E
from repro.core.gemm.shapes import PAPER_IRREGULAR_SHAPES
from repro.kernels.ftimm.epilogue import Epilogue

REPO = os.path.join(os.path.dirname(__file__), "..")
COMMITTED_CACHE = os.path.join(REPO, "results", "plan_cache.json")


def _codes(violations):
    return {v.code for v in contracts.errors(violations)}


# ---------------------------------------------------------------------------
# Every currently shipped candidate passes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (65536, 32, 32),        # paper T1
    (32, 1048576, 32),      # paper T2
    (20480, 20480, 96),     # paper T3
    (4097, 999, 31),        # worst-case unaligned edge
    (128, 4096, 14336),     # decode MLP
])
@pytest.mark.parametrize("width", [4, 2])
def test_shipped_dense_candidates_pass(m, k, n, width):
    for epi_ops in (0, 2):
        cands = tuner.gemm_candidates(m, k, n, width, width, TPU_V5E,
                                      epi_ops)
        assert cands
        for p in cands:
            vs = contracts.check_plan("dense", (m, k, n), p, in_bytes=width,
                                      out_bytes=width, coverage=True)
            assert not contracts.errors(vs), (p, [str(v) for v in vs])


def test_shipped_batched_and_ragged_candidates_pass():
    for g, m, k, n in [(8, 128, 4096, 14336), (16, 96, 1000, 31)]:
        for p in tuner.batched_candidates(g, m, k, n, 4, 4, "none", TPU_V5E):
            vs = contracts.check_plan("batched", (g, m, k, n), p,
                                      coverage=True)
            assert not contracts.errors(vs), (p, [str(v) for v in vs])
    for g, t, k, n in [(8, 1024, 4096, 14336), (64, 0, 4096, 1024),
                       (16, 100, 64, 31)]:
        for ragged in ("m", "k"):
            for p in tuner.ragged_candidates(g, t, k, n, 4, 4, ragged,
                                             TPU_V5E):
                vs = contracts.check_plan("ragged", (g, t, k, n), p,
                                          ragged=ragged)
                assert not contracts.errors(vs), (p, [str(v) for v in vs])


def test_kernel_bodies_mask_all_operands():
    assert contracts.check_contraction_masking() == []


def test_shipped_ragged_metadata_sorted():
    for offsets in ([0, 100, 228, 1024], [0, 0, 64, 64, 640], [0, 7],
                    [0, 16, 16], [0, 512]):
        for bm in (8, 64, 128):
            vs = contracts.check_ragged_visit_plan(offsets, bm)
            assert not contracts.errors(vs), (offsets, bm,
                                              [str(v) for v in vs])


# ---------------------------------------------------------------------------
# Mutation tests: each corruption must be flagged
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def base_plan():
    return tuner.plan_gemm(4096, 4096, 4096)


def test_mutation_unclamped_bk(base_plan):
    # The PR 5 bug class: cached bk=512 against K=64 pads K 8-fold.
    p = dataclasses.replace(base_plan, bk=512)
    assert "unclamped_block" in _codes(
        contracts.check_plan("dense", (4096, 64, 4096), p))


def test_mutation_misaligned_blocks(base_plan):
    p = dataclasses.replace(base_plan, bm=100)
    assert "misaligned_block" in _codes(
        contracts.check_plan("dense", (4096, 4096, 4096), p))
    p = dataclasses.replace(base_plan, bn=96)
    assert "misaligned_block" in _codes(
        contracts.check_plan("dense", (4096, 4096, 4096), p))


def test_mutation_over_budget_accumulator(base_plan):
    p = dataclasses.replace(base_plan, bm=4096, bn=4096, bk=128)
    assert "vmem_budget" in _codes(
        contracts.check_plan("dense", (4096, 4096, 4096), p))


def test_mutation_tn_bm_on_lane_axis(base_plan):
    # "tn" blocks A as (bk, bm): bm is a lane extent there, so the
    # sublane-sized bm a small-M "nn" plan carries is refused by Mosaic.
    p = dataclasses.replace(base_plan, bm=16, bn=128, bk=2048)
    dims = (8, 2048, 6144)
    kw = dict(in_bytes=2, out_bytes=2)
    assert "misaligned_block" not in _codes(
        contracts.check_plan("dense", dims, p, **kw))
    assert "misaligned_block" in _codes(
        contracts.check_plan("dense", dims, p, trans="tn", **kw))
    assert "misaligned_block" in _codes(
        contracts.check_plan("batched", (4,) + dims, p, trans="tn", **kw))
    # The ragged dW blocks x as (bk, bm): D (bm) is its lane axis.
    assert "misaligned_block" in _codes(contracts.check_blocks(
        "ragged", (8, 1024, 2048, 512), bm=16, bn=128, bk=128, ragged="k"))
    # The planners emit lane-aligned bm wherever M is the minor axis.
    assert tuner.plan_gemm(8, 2048, 6144, 2, 2, trans="tn").bm % 128 == 0
    assert tuner.plan_batched_gemm(4, 8, 2048, 6144, 2, 2,
                                   trans="tn").bm % 128 == 0
    assert tuner.plan_ragged_gemm(8, 1024, 16, 512, 2, 2,
                                  ragged="k").bm % 128 == 0


def test_mutation_vmem_extras_over_budget(base_plan):
    # Base blocks fit; the residual block + bias row push the working set
    # past the budget.  That plan is an error now, not a warning.
    p = dataclasses.replace(base_plan, bm=1024, bn=1024, bk=512)
    epi = Epilogue(bias=True, residual=True)
    kw = dict(bm=1024, bn=1024, bk=512, in_bytes=2, out_bytes=2)
    assert contracts.vmem_footprint("dense", **kw) <= TPU_V5E.vmem_budget
    assert contracts.vmem_footprint("dense", epilogue=epi,
                                    **kw) > TPU_V5E.vmem_budget
    vs = contracts.check_plan("dense", (4096, 4096, 4096), p, in_bytes=2,
                              out_bytes=2, epilogue=epi)
    assert "vmem_budget_extras" in _codes(vs)
    # The planner admits on the same full footprint.
    plan = tuner.plan_gemm(512, 6144, 2048, 2, 2, epi_ops=epi.num_ops,
                           epilogue=epi)
    assert not contracts.errors(contracts.check_plan(
        "dense", (512, 6144, 2048), plan, in_bytes=2, out_bytes=2,
        epilogue=epi))


def test_mutation_splitk_nonlinear_epilogue(base_plan):
    p = dataclasses.replace(base_plan, nsplit=2, bk=128, fuse=True)
    vs = contracts.check_plan("dense", (4096, 4096, 4096), p,
                              epilogue=Epilogue(activation="silu"))
    assert "splitk_nonlinear_epilogue" in _codes(vs)
    # The linear tail stays legal (applied post-reduction).
    vs = contracts.check_plan("dense", (4096, 4096, 4096), p,
                              epilogue=Epilogue(bias=True))
    assert "splitk_nonlinear_epilogue" not in _codes(vs)


def test_mutation_nonpositive_and_bad_order(base_plan):
    p = dataclasses.replace(base_plan, bk=0)
    assert "nonpositive_block" in _codes(
        contracts.check_plan("dense", (4096, 4096, 4096), p))
    p = dataclasses.replace(base_plan, dim_order="km")
    assert "bad_dim_order" in _codes(
        contracts.check_plan("dense", (4096, 4096, 4096), p))


def test_mutation_overlapping_index_map(base_plan):
    # Corrupted BlockSpec: two parallel grid points store the same block.
    c = contracts.variant_contract("dense", (4096, 4096, 4096),
                                   dataclasses.replace(base_plan, bk=128))
    bad = dataclasses.replace(c, out_index_map=lambda i, j, k: (i // 2, j))
    codes = {v.code for v in contracts.verify_contract(bad)}
    assert "write_race" in codes and "coverage_gap" in codes


def test_mutation_store_moves_with_reduction(base_plan):
    c = contracts.variant_contract("dense", (4096, 4096, 4096),
                                   dataclasses.replace(base_plan, bk=128))
    bad = dataclasses.replace(
        c, out_index_map=lambda i, j, k: (i, (j + k) % c.out_extent[1]))
    codes = {v.code for v in contracts.verify_contract(bad)}
    assert "store_moves_with_reduction" in codes


def test_mutation_out_of_range_store(base_plan):
    c = contracts.variant_contract("dense", (4096, 4096, 4096),
                                   dataclasses.replace(base_plan, bk=128))
    bad = dataclasses.replace(c, out_index_map=lambda i, j, k: (i + 1, j))
    assert "out_of_range_store" in {v.code
                                    for v in contracts.verify_contract(bad)}


def _single_masked_body(a_blk, b_blk, k_lim):
    # Deliberately unsound: masks A only; 0 * NaN from B's remainder leaks.
    a_blk = _mask_contract(a_blk, k_lim, 1)     # noqa: F821
    return a_blk @ b_blk


def test_mutation_missing_k_mask():
    vs = contracts.check_contraction_masking(accum_body=_single_masked_body)
    assert "missing_k_mask" in {v.code for v in vs}
    assert contracts.masked_operand_count(_single_masked_body) == 1


def test_mutation_shuffled_visit_list():
    # A reordering regression in the sorted visit list must be caught
    # statically: the masked read-modify-write is the ordered exception.
    vs = contracts.check_ragged_visits([0, 100, 228], 2, 128,
                                       gids=[1, 0], tids=[1, 0],
                                       valid=[1, 1])
    codes = _codes(vs)
    assert "unsorted_visits" in codes and "unsorted_groups" in codes
    vs = contracts.check_ragged_visits([0, 100, 228], 2, 128,
                                       gids=[0, 0], tids=[0, 0],
                                       valid=[1, 1])
    codes = _codes(vs)
    assert "duplicate_visit" in codes and "ragged_row_uncovered" in codes


def test_mutation_ep_indivisible():
    placement = tuner.Placement(strategy="expert_parallel", num_shards=3)
    assert "ep_indivisible" in _codes(
        contracts.check_placement("ragged", (8, 1024, 256, 256), placement))
    ok = tuner.Placement(strategy="expert_parallel", num_shards=4)
    assert not contracts.errors(
        contracts.check_placement("ragged", (8, 1024, 256, 256), ok))


# ---------------------------------------------------------------------------
# Plan-store quarantine + telemetry
# ---------------------------------------------------------------------------

def test_plan_store_quarantines_bad_records(tmp_path):
    path = tmp_path / "cache.json"
    blob = {"schema": plan_store.SCHEMA_VERSION,
            "device_kind": plan_store.device_kind(),
            "entries": {
                "dense|4096x64x4096|ib4|ob4":
                    {"bm": 128, "bn": 128, "bk": 512},    # unclamped bk
                "dense|4096x4096x4096|ib4|ob4":
                    {"bm": 128, "bn": 128, "bk": 128},    # fine
                "garbage-key": {"bm": 128, "bn": 128, "bk": 128},
            }}
    path.write_text(json.dumps(blob))
    st = plan_store.PlanStore()
    n = st.load(str(path))
    assert n == 1
    assert set(st.quarantined) == {"dense|4096x64x4096|ib4|ob4",
                                   "garbage-key"}
    assert st.quarantined["dense|4096x64x4096|ib4|ob4"] == \
        ["unclamped_block"]
    assert st.lookup("dense|4096x4096x4096|ib4|ob4") is not None
    assert st.lookup("dense|4096x64x4096|ib4|ob4") is None
    st.clear()
    assert not st.quarantined


def test_quarantine_counted_in_plan_mode_stats(tmp_path):
    path = tmp_path / "cache.json"
    blob = {"schema": plan_store.SCHEMA_VERSION,
            "device_kind": plan_store.device_kind(),
            "entries": {"dense|512x64x512|ib4|ob4":
                        {"bm": 128, "bn": 128, "bk": 1024}}}
    path.write_text(json.dumps(blob))
    tuner.clear_plan_cache()
    try:
        plan_store.get_store().load(str(path))
        stats = tuner.plan_mode_stats()
        assert stats["dense"]["quarantined"] == 1
    finally:
        tuner.clear_plan_cache()


# ---------------------------------------------------------------------------
# Committed-cache round-trip: pruning changes no chosen plan
# ---------------------------------------------------------------------------

def test_committed_cache_records_all_pass():
    blob = json.load(open(COMMITTED_CACHE))
    assert blob["entries"]
    for key, rec in blob["entries"].items():
        vs = contracts.errors(contracts.check_record(key, rec))
        assert not vs, (key, [str(v) for v in vs])


def test_candidate_pruning_roundtrip_on_committed_cache():
    blob = json.load(open(COMMITTED_CACHE))
    for key in blob["entries"]:
        pk = contracts.parse_key(key)
        assert pk is not None and pk.family == "dense"
        m, k, n = pk.dims
        for epi_ops in (0, 2):
            with_check = tuner.gemm_candidates(
                m, k, n, pk.in_bytes, pk.out_bytes, TPU_V5E, epi_ops,
                verify=True)
            without = tuner.gemm_candidates(
                m, k, n, pk.in_bytes, pk.out_bytes, TPU_V5E, epi_ops,
                verify=False)
            pick = lambda cs: min(cs, key=lambda p: p.est.t_total)  # noqa: E731
            assert pick(with_check) == pick(without), key
            assert set(with_check) == set(without), key


# ---------------------------------------------------------------------------
# REPRO_VERIFY=1 dispatch mode
# ---------------------------------------------------------------------------

def test_repro_verify_accepts_planned_calls(monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setenv("REPRO_VERIFY", "1")
    a = jnp.ones((100, 70), jnp.float32)
    b = jnp.ones((70, 50), jnp.float32)
    y = dispatch.matmul(a, b, epilogue=Epilogue(bias=True),
                        bias=jnp.ones((50,), jnp.float32))
    np.testing.assert_allclose(np.asarray(y), 71.0)


def test_repro_verify_rejects_corrupt_plan(monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setenv("REPRO_VERIFY", "1")
    good = tuner.plan_gemm(96, 64, 48, 4, 4)
    corrupt = dataclasses.replace(good, bk=2048)    # unclamped vs K=64
    monkeypatch.setattr(dispatch, "plan_gemm",
                        lambda *a, **kw: corrupt)
    dispatch._verify_cached.cache_clear()
    with pytest.raises(contracts.ContractError, match="unclamped_block"):
        dispatch.matmul(jnp.ones((96, 64), jnp.float32),
                        jnp.ones((64, 48), jnp.float32))
    dispatch._verify_cached.cache_clear()


def test_repro_verify_off_skips_checks(monkeypatch):
    import jax.numpy as jnp
    monkeypatch.delenv("REPRO_VERIFY", raising=False)
    good = tuner.plan_gemm(96, 64, 48, 4, 4)
    corrupt = dataclasses.replace(good, bk=2048)
    monkeypatch.setattr(dispatch, "plan_gemm", lambda *a, **kw: corrupt)
    # XLA backend ignores blocks; without REPRO_VERIFY the bad plan is
    # only a bad *decision*, not an assertion failure.
    y = dispatch.matmul(jnp.ones((96, 64), jnp.float32),
                        jnp.ones((64, 48), jnp.float32))
    assert y.shape == (96, 48)


# ---------------------------------------------------------------------------
# Ragged zero-copy edge path (satellite bugfix)
# ---------------------------------------------------------------------------

def test_ragged_wrappers_skip_pad_when_aligned(monkeypatch):
    import jax.numpy as jnp
    from repro.kernels.ftimm import ops

    calls = []
    orig = ops._pad_to

    def counting(x, shape):
        calls.append(shape)
        return orig(x, shape)

    monkeypatch.setattr(ops, "_pad_to", counting)
    # Unique block-aligned shapes (fresh jit trace so the counter sees it).
    x = jnp.ones((384, 256), jnp.float32)
    w = jnp.ones((3, 256, 384), jnp.float32)
    off = jnp.asarray([0, 128, 200, 384], jnp.int32)
    y = ops.ragged_gemm(x, w, off, bm=64, bn=128, bk=128, interpret=True)
    assert calls == [] and y.shape == (384, 384)
    dw = ops.ragged_gemm_dw(x, jnp.ones((384, 128), jnp.float32), off,
                            bm=128, bn=128, bk=64, interpret=True)
    assert calls == [] and dw.shape == (3, 256, 128)
    # Unaligned rows still pad (and still compute correctly).
    xu = jnp.ones((250, 256), jnp.float32)
    offu = jnp.asarray([0, 128, 200, 250], jnp.int32)
    yu = ops.ragged_gemm(xu, w, offu, bm=64, bn=128, bk=128,
                         interpret=True)
    assert calls and yu.shape == (250, 384)
    np.testing.assert_allclose(np.asarray(yu), 256.0)


def test_ragged_aligned_matches_unaligned_numerics(rng_key):
    import jax
    import jax.numpy as jnp
    from repro.kernels.ftimm import ops
    k1, k2 = jax.random.split(rng_key)
    x = jax.random.normal(k1, (256, 128), jnp.float32)
    w = jax.random.normal(k2, (4, 128, 256), jnp.float32)
    off = jnp.asarray([0, 64, 100, 200, 256], jnp.int32)
    y = ops.ragged_gemm(x, w, off, bm=64, bn=128, bk=128, interpret=True)
    bounds = np.asarray(off)
    ref = np.concatenate([
        np.asarray(x)[s:e] @ np.asarray(w)[i]
        for i, (s, e) in enumerate(zip(bounds[:-1], bounds[1:]))])
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# The sweep itself
# ---------------------------------------------------------------------------

def test_run_sweep_quick_zero_violations():
    report = run_sweep(shapes=PAPER_IRREGULAR_SHAPES[:3],
                       archs=["qwen3-1.7b", "mixtral-8x7b"],
                       cache_path=COMMITTED_CACHE)
    assert report["violations"] == [], report["violations"][:5]
    assert report["candidates_checked"] > 100
    assert report["plan_cache"]["entries"] == 28
    assert report["plan_cache"]["quarantine_candidates"] == 0
