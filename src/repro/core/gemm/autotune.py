"""Measured auto-tuning: on-device search over CMR-shortlisted plans.

ftIMM's third pillar is auto-tuning of block sizes and parallelization
strategies; until now the repo's "tuning" was purely analytic — every
``plan_*`` takes the argmin of the CMR model, which is never validated
against hardware.  This module closes the loop the way Catalán et al.
(arXiv:1506.08988) prescribe — measurement-driven configuration on top of a
*model-pruned* search space:

  1. **Shortlist** — the shared candidate generator (``tuner.*_candidates``)
     enumerates every feasible tiling, the CMR model ranks them, and the
     top-K (analytic argmin first) survive to the device.
  2. **Measure** — a common timing harness compiles and times each survivor
     (jit + ``block_until_ready``, median of R repeats) through the ops
     layer's block-parameterized wrappers DIRECTLY — never through the plan
     cache it is validating.  Oversized problems are scaled down (largest
     dims halved under an element budget) so the harness runs everywhere;
     an interpret-mode engine exists for hosts without a TPU.
  3. **Remember** — the winner lands in the persistent ``plan_store`` keyed
     by (device kind, family, shape signature, dtype widths, placement
     request); ``plan_gemm``/``plan_batched_gemm``/``plan_ragged_gemm``
     consult it before their analytic argmin and tag served plans
     ``mode == "cached"``.
  4. **Calibrate** — ``calibrate`` fits the effective ``TpuSpec`` constants
     (achievable-flops fraction, effective HBM bandwidth) from
     measured-vs-predicted ratios, and ``calibrate_ici`` fits the
     effective-ICI-bandwidth fraction from timed mesh collectives, so
     *unmeasured* shapes plan against corrected rooflines AND corrected
     wires too (``tuner.effective_spec``).

Timing engines (``engine=``):

  * ``"pallas"`` — the real ftIMM kernels (TPU).  Fully plan-dependent.
  * ``"pallas_interpret"`` — the same kernels in interpret mode: slow, but
    plan-dependent (grid geometry is executed) and runs on any host.
  * ``"xla"`` — the XLA reference GEMM on operands padded to the candidate's
    block multiples.  Fast everywhere; differentiates candidates only
    through their padding waste (the execution itself is untiled), so on
    CPU it mostly *validates* the analytic choice and feeds calibration.

Placed searches (``num_shards > 1``) are hybrid: the per-shard local GEMM of
each ``tuner.PlacementOption`` is measured, the ICI collective term stays
modeled (there is no mesh inside the harness, but ``calibrate_ici``
corrects the modeled wires from timed mesh exchanges), and the same
clear-win margins arbitrate — measured compute, calibrated-model wires.
Overlapped (``schedule == "ring"``) options compose local and collective
time as MAX, unoverlapped as SUM, in both the measured and analytic
scores.  ``time_placed_ragged_e2e``/``time_placed_dense_e2e`` go one step
further: they run the placed executors end-to-end on a real mesh —
collectives executed, not priced — for crossover-agreement checks.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from ...kernels.ftimm import ops as _ops
from ...kernels.ftimm import ref as _ref
from ...kernels.ftimm.epilogue import Epilogue
from . import plan_store, tuner
from .cmr import (TPU_V5E, PlanEstimate, TpuSpec, ceil_to, estimate,
                  estimate_batched, estimate_ep, estimate_ragged)
from .plan_store import Calibration
from .tuner import GemmPlan

DEFAULT_TOP_K = 4
DEFAULT_REPEATS = 3
DEFAULT_MAX_ELEMENTS = 1 << 22      # per-sweep operand-element budget


def default_engine() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


_ENGINES = ("xla", "pallas", "pallas_interpret")


def _check_engine(engine: str) -> str:
    if engine not in _ENGINES:
        raise ValueError(f"unknown timing engine: {engine!r} "
                         f"(expected one of {_ENGINES})")
    return engine


def _dtype(nbytes: int):
    try:
        return {4: jnp.float32, 2: jnp.bfloat16, 1: jnp.int8}[int(nbytes)]
    except KeyError:
        raise ValueError(
            f"unsupported operand width for measured tuning: {nbytes} bytes "
            "(4 = float32, 2 = bfloat16, 1 = int8)") from None


def _rand(shape, dtype, seed: int = 0):
    if jnp.dtype(dtype) == jnp.int8:
        # Full-range int8 operands: timing is value-independent, but keep
        # the panels representative of real quantized weights anyway.
        return jax.random.randint(jax.random.PRNGKey(seed), shape,
                                  -127, 128, jnp.int32).astype(jnp.int8)
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


@dataclass(frozen=True)
class TuneResult:
    """Outcome of one measured search.

    ``plan`` is the winner for the ORIGINAL dims (analytic estimate
    attached, ``mode == "measured"``).  Times are wall-clock seconds of the
    *measured problem* — ``measured_dims``, the original shape scaled into
    the harness's element budget — so ``t_measured <= t_analytic`` holds on
    the same run by construction (the analytic argmin is always candidate
    zero of the shortlist).  ``est_measured`` is the analytic estimate of
    that measured problem under the winner's tiling: the (prediction,
    measurement) pair calibration consumes."""
    family: str
    dims: tuple
    measured_dims: tuple
    key: str
    plan: GemmPlan
    t_measured: float
    t_analytic: float
    analytic_plan: GemmPlan
    est_measured: PlanEstimate
    engine: str
    timed: tuple                    # ((bm, bn, bk, dim_order, seconds), ...)
    in_bytes: int = 4               # operand width (1 routes the int8 peak)
    b_bytes: int | None = None      # mixed-width B operand, None = same as A

    @property
    def ratio_pred_over_meas(self) -> float:
        return self.est_measured.t_total / max(self.t_measured, 1e-12)


# ---------------------------------------------------------------------------
# Shape scaling: keep the harness inside an element budget by halving the
# largest shrinkable dims (never N — irregularity lives in M/K/G).
# ---------------------------------------------------------------------------

_SCALE_FLOOR = 4096


def _scale2(a: int, b: int, budget_check) -> tuple[int, int]:
    """Halve the larger of two shrinkable dims until the budget holds or
    both hit the floor."""
    while not budget_check(a, b):
        if a >= b and a > _SCALE_FLOOR:
            a = max(a // 2, _SCALE_FLOOR)
        elif b > _SCALE_FLOOR:
            b = max(b // 2, _SCALE_FLOOR)
        elif a > _SCALE_FLOOR:
            a = max(a // 2, _SCALE_FLOOR)
        else:
            break
    return a, b


def _scale_dense(m: int, k: int, n: int, budget: int) -> tuple[int, int, int]:
    m, k = _scale2(m, k, lambda a, b: a * b + b * n + a * n <= budget)
    return m, k, n


def _scale_batched(g: int, m: int, k: int, n: int,
                   budget: int) -> tuple[int, int, int, int]:
    per = m * k + k * n + m * n
    while g * per > budget and g > 4:
        g = max(g // 2, 4)
    m, k = _scale2(m, k,
                   lambda a, b: g * (a * b + b * n + a * n) <= budget)
    return g, m, k, n


def _scale_ragged(g: int, total: int, k: int, n: int,
                  budget: int) -> tuple[int, int, int, int]:
    floor_t = max(_SCALE_FLOOR, 2 * g)
    while total * (k + n) + g * k * n > budget and total > floor_t:
        total = max(total // 2, floor_t)
    while total * (k + n) + g * k * n > budget and k > _SCALE_FLOOR:
        k = max(k // 2, _SCALE_FLOOR)
    return g, total, k, n


def _balanced_offsets(g: int, total: int) -> jnp.ndarray:
    import numpy as np
    return jnp.asarray(np.rint(np.linspace(0, total, g + 1)).astype(np.int32))


# ---------------------------------------------------------------------------
# Per-family timing runners.  Each returns (signature, thunk): candidates
# whose executed computation coincides share one measurement (no noise
# mining between physically identical runs).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jit_dense_ref(out_dtype_name: str):
    od = jnp.dtype(out_dtype_name)
    return jax.jit(lambda a, b: _ref.matmul_nn(a, b, od))


@functools.lru_cache(maxsize=None)
def _jit_batched_ref(out_dtype_name: str, a_ndim: int, b_ndim: int):
    od = jnp.dtype(out_dtype_name)
    al = "gmk" if a_ndim == 3 else "mk"
    bl = "gkn" if b_ndim == 3 else "kn"

    def f(a, b):
        out = jnp.einsum(f"{al},{bl}->gmn", a, b,
                         preferred_element_type=jnp.float32)
        return out.astype(od)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _jit_ragged_ref(out_dtype_name: str):
    od = jnp.dtype(out_dtype_name)

    def f(x, w, offsets):
        sizes = jnp.diff(offsets).astype(jnp.int32)
        return jax.lax.ragged_dot(
            x, w, sizes, preferred_element_type=jnp.float32).astype(od)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _jit_ragged_dw_ref(out_dtype_name: str):
    od = jnp.dtype(out_dtype_name)
    return jax.jit(functools.partial(_ref.ragged_matmul_dw_ref, out_dtype=od))


def _clamp_blocks(plan: GemmPlan, bm_top: int, bn_top: int,
                  bk_top: int) -> tuple[int, int, int]:
    return (min(plan.bm, bm_top), min(plan.bn, bn_top), min(plan.bk, bk_top))


@functools.lru_cache(maxsize=None)
def _jit_epilogue(epi: Epilogue, out_dtype_name: str):
    """One compiled tail pass over a stored output."""
    od = jnp.dtype(out_dtype_name)
    return jax.jit(lambda y, bias, res: epi.apply(
        y.astype(jnp.float32), bias=bias, residual=res).astype(od))


def _tail_passes(epi: Epilogue, out_dtype, fused: bool):
    """The tail as compiled passes: the FUSED candidate runs it as one pass
    (its cost is an upper bound — on the TPU kernels it is zero, folded into
    the accumulator flush; an XLA:CPU emitter quirk makes a tail inlined
    into the dot jit run single-threaded, i.e. slower than a standalone
    pass, so inline fusion is deliberately not what this harness times),
    the UNFUSED candidate as one separate pass per op — the extra HBM
    round-trips ``cmr._epilogue_bytes`` prices."""
    specs = (epi,) if fused else epi.decompose()
    return [_jit_epilogue(s, jnp.dtype(out_dtype).name) for s in specs]


def _epi_operands(epi: Epilogue | None, m: int, n: int, dtype):
    if epi is None:
        return None, None
    # Flush vectors stay float even when the GEMM operands are quantized.
    vdt = dtype if jnp.dtype(dtype).itemsize > 1 else jnp.float32
    bias = _rand((n,), vdt, seed=2) if epi.bias else None
    res = _rand((m, n), vdt, seed=3) if epi.residual else None
    return bias, res


def _dense_runner(engine, a, b, plan, out_dtype, epi: Epilogue | None = None):
    m, k = a.shape
    n = b.shape[1]
    sub = _ops.sublane(a.dtype)
    bm, bn, bk = _clamp_blocks(plan, ceil_to(m, sub), ceil_to(n, 128),
                               ceil_to(k, 128))
    bias, res = _epi_operands(epi, m, n, a.dtype)
    fused = epi is not None and plan.fuse

    def with_tail(thunk, passes):
        """Chain tail passes over the GEMM result (sliced to the true shape
        first when the padded engine produced a padded output)."""
        if not passes:
            return thunk

        def run():
            y = thunk()[:m, :n]
            for p in passes:
                y = p(y, bias, res)
            return y

        return run

    if engine == "xla":
        if plan.edge == "padded":
            mp, kp, np_ = ceil_to(m, bm), ceil_to(k, bk), ceil_to(n, bn)
            a_p = jnp.pad(a, ((0, mp - m), (0, kp - k)))
            b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
        else:
            mp, kp, np_ = m, k, n
            a_p, b_p = a, b
        fn = _jit_dense_ref(jnp.dtype(out_dtype).name)
        passes = [] if epi is None else _tail_passes(epi, out_dtype, fused)
        return (("xla", mp, kp, np_, epi, fused),
                with_tail(lambda: fn(a_p, b_p), passes))
    interp = engine == "pallas_interpret"
    sig = ("pl", bm, bn, bk, plan.dim_order, plan.edge, interp, epi, fused)
    kw = dict(bm=bm, bn=bn, bk=bk, dim_order=plan.dim_order,
              out_dtype=out_dtype, interpret=interp, edge=plan.edge)
    if fused:
        # True in-kernel fusion: the tail rides the accumulator flush.
        return sig, (lambda: _ops.gemm(a, b, epilogue=epi, bias=bias,
                                       residual=res, **kw))
    return sig, with_tail(lambda: _ops.gemm(a, b, **kw),
                          [] if epi is None
                          else _tail_passes(epi, out_dtype, False))


def _batched_runner(engine, a, b, plan, out_dtype):
    m, k = a.shape[-2:]
    n = b.shape[-1]
    sub = _ops.sublane(a.dtype)
    bm, bn, bk = _clamp_blocks(plan, ceil_to(m, sub), ceil_to(n, 128),
                               ceil_to(k, 128))
    if engine == "xla":
        if plan.edge == "padded":
            mp, kp, np_ = ceil_to(m, bm), ceil_to(k, bk), ceil_to(n, bn)

            def pad(x, last2):
                pads = [(0, 0)] * (x.ndim - 2) + \
                    [(0, t - s) for s, t in zip(x.shape[-2:], last2)]
                return jnp.pad(x, pads)

            a_p, b_p = pad(a, (mp, kp)), pad(b, (kp, np_))
        else:
            mp, kp, np_ = m, k, n
            a_p, b_p = a, b
        fn = _jit_batched_ref(jnp.dtype(out_dtype).name, a.ndim, b.ndim)
        return ("xla", mp, kp, np_), (lambda: fn(a_p, b_p))
    interp = engine == "pallas_interpret"
    sig = ("pl", bm, bn, bk, plan.dim_order, plan.edge, interp)
    return sig, (lambda: _ops.batched_gemm(
        a, b, bm=bm, bn=bn, bk=bk, dim_order=plan.dim_order,
        out_dtype=out_dtype, interpret=interp, edge=plan.edge))


def _ragged_runner(engine, x, w, offsets, plan, out_dtype, ragged):
    total, k = x.shape
    if ragged == "k":
        # dW: x (T, D), w is dy (T, F); the ragged dim is the contraction.
        if engine == "xla":
            fn = _jit_ragged_dw_ref(jnp.dtype(out_dtype).name)
            return ("xla", "dw"), (lambda: fn(x, w, offsets))
        interp = engine == "pallas_interpret"
        sig = ("pl", plan.bm, plan.bn, plan.bk, interp)
        return sig, (lambda: _ops.ragged_gemm_dw(
            x, w, offsets, bm=plan.bm, bn=plan.bn, bk=plan.bk,
            out_dtype=out_dtype, interpret=interp))
    n = w.shape[2]
    sub = _ops.sublane(x.dtype)
    bm, bn, bk = _clamp_blocks(plan, ceil_to(total, sub), ceil_to(n, 128),
                               ceil_to(k, 128))
    if engine == "xla":
        tp = ceil_to(total, bm)
        x_p = jnp.pad(x, ((0, tp - total), (0, 0)))
        offs = offsets.at[-1].set(tp)       # pad rows ride the last group
        fn = _jit_ragged_ref(jnp.dtype(out_dtype).name)
        return ("xla", tp), (lambda: fn(x_p, w, offs))
    interp = engine == "pallas_interpret"
    sig = ("pl", bm, bn, bk, interp)
    return sig, (lambda: _ops.ragged_gemm(
        x, w, offsets, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
        interpret=interp))


def _measure_shortlist(sl, make_runner, repeats):
    """Time each shortlisted candidate (memoized on the executed-computation
    signature) and return (times, winner_index).  Ties keep the earliest —
    i.e. the analytic argmin, which is always index 0."""
    memo: dict = {}
    times: list[float] = []
    for cand in sl:
        sig, thunk = make_runner(cand)
        if sig not in memo:
            memo[sig] = _ops.bench(thunk, repeats=repeats)
        times.append(memo[sig])
    widx = min(range(len(sl)), key=lambda i: (times[i], i))
    return times, widx


def _store_result(res: TuneResult, *, num_shards: int = 1,
                  strategy: str | None = None,
                  schedule: str | None = None) -> None:
    rec = {
        "bm": res.plan.bm, "bn": res.plan.bn, "bk": res.plan.bk,
        "nsplit": res.plan.nsplit, "dim_order": res.plan.dim_order,
        "edge": res.plan.edge, "fuse": res.plan.fuse,
        "t_measured_us": round(res.t_measured * 1e6, 3),
        "t_analytic_us": round(res.t_analytic * 1e6, 3),
        "t_model_us": round(res.est_measured.t_total * 1e6, 6),
        "engine": res.engine, "mode": "measured",
    }
    if strategy is not None:
        rec["strategy"] = strategy
        if schedule is not None:
            rec["schedule"] = schedule
    plan_store.get_store().put(res.key, rec)
    tuner.clear_planner_caches()    # next plan_* consults the new entry


def time_dense_plans(m: int, k: int, n: int, plans, *,
                     in_bytes: int = 4, out_bytes: int = 4,
                     engine: str | None = None,
                     repeats: int = DEFAULT_REPEATS,
                     max_elements: int = DEFAULT_MAX_ELEMENTS,
                     epilogue: Epilogue | None = None) -> list[float]:
    """Time an explicit list of dense plans on the harness (one shared
    scaled problem, physically-identical runs memoized) — the replay path:
    no search, no store, just seconds per plan.  ``epilogue`` times each
    plan WITH the elementwise tail, fused or separate per its ``fuse``."""
    engine = _check_engine(engine or default_engine())
    mm, kk, nn = _scale_dense(m, k, n, max_elements)
    in_dt, out_dt = _dtype(in_bytes), _dtype(out_bytes)
    a, b = _rand((mm, kk), in_dt), _rand((kk, nn), in_dt, seed=1)
    times, _ = _measure_shortlist(
        list(plans),
        lambda c: _dense_runner(engine, a, b, c, out_dt, epilogue),
        repeats)
    return times


# ---------------------------------------------------------------------------
# Family searches
# ---------------------------------------------------------------------------

def autotune_gemm(
    m: int, k: int, n: int,
    in_bytes: int = 4,
    out_bytes: int = 4,
    spec: TpuSpec = TPU_V5E,
    *,
    num_shards: int = 1,
    axis: str | None = None,
    top_k: int = DEFAULT_TOP_K,
    repeats: int = DEFAULT_REPEATS,
    engine: str | None = None,
    max_elements: int = DEFAULT_MAX_ELEMENTS,
    store: bool = True,
    epilogue: Epilogue | None = None,
    b_bytes: int | None = None,
) -> TuneResult:
    """Measured search for the dense GEMM: CMR shortlist -> time -> winner
    (``mode == "measured"``), persisted to the plan store unless
    ``store=False``.  ``num_shards > 1`` runs the hybrid placed search
    (measured local GEMM per strategy + modeled collective).

    ``epilogue`` widens the search to the fusion decision: candidates fork
    on running the elementwise tail in the accumulator flush (``fuse=True``)
    vs as separate compiled passes over the stored output, and every
    candidate is timed WITH its tail — so the persisted winner records
    whether fusion actually paid on this engine, not just in the model.

    ``b_bytes`` searches the MIXED-width dtype axis (weight-only quant:
    ``in_bytes``-wide A against a ``b_bytes``-wide B panel); the winner is
    stored under the ``+bb{n}`` key fragment so only mixed-width calls are
    served by it."""
    engine = _check_engine(engine or default_engine())
    epi_ops = epilogue.num_ops if epilogue is not None else 0
    # Shortlist under the calibrated view (better pruning), but express
    # est_measured in the RAW base spec: calibration fractions are absolute
    # w.r.t. that spec, so fitting against already-calibrated predictions
    # would collapse a re-calibration to ~1.0 and destroy the correction.
    base_spec = spec
    spec = tuner.effective_spec(spec)
    if num_shards > 1:
        opts = tuner.dense_placement_options(m, k, n, num_shards, in_bytes,
                                             out_bytes, spec, axis)
        return _tune_placed(
            "dense", (m, k, n), opts, in_bytes, out_bytes, spec,
            lambda dims: autotune_gemm(
                *dims, in_bytes, out_bytes, spec, top_k=top_k,
                repeats=repeats, engine=engine, max_elements=max_elements,
                store=False, epilogue=epilogue),
            num_shards=num_shards, engine=engine, store=store)

    cands = tuner.gemm_candidates(m, k, n, in_bytes, out_bytes, spec,
                                  epi_ops, b_bytes=b_bytes)
    sl = tuner.shortlist(cands, top_k)
    mm, kk, nn = _scale_dense(m, k, n, max_elements)
    in_dt, out_dt = _dtype(in_bytes), _dtype(out_bytes)
    b_dt = in_dt if b_bytes is None else _dtype(b_bytes)
    a, b = _rand((mm, kk), in_dt), _rand((kk, nn), b_dt, seed=1)
    times, widx = _measure_shortlist(
        sl, lambda c: _dense_runner(engine, a, b, c, out_dt, epilogue),
        repeats)
    winner = replace(sl[widx], mode="measured")
    est_meas = estimate(mm, kk, nn, bm=winner.bm, bn=winner.bn, bk=winner.bk,
                        dim_order=winner.dim_order, in_bytes=in_bytes,
                        out_bytes=out_bytes, edge=winner.edge,
                        epi_ops=epi_ops, epi_fused=winner.fuse,
                        spec=base_spec, b_bytes=b_bytes)
    res = TuneResult(
        family="dense", dims=(m, k, n), measured_dims=(mm, kk, nn),
        key=plan_store.shape_key("dense", (m, k, n), in_bytes, out_bytes,
                                 extra=tuner._dtype_extra(b_bytes)),
        plan=winner, t_measured=times[widx], t_analytic=times[0],
        analytic_plan=sl[0], est_measured=est_meas, engine=engine,
        timed=tuple((c.bm, c.bn, c.bk, c.dim_order, t)
                    for c, t in zip(sl, times)),
        in_bytes=in_bytes, b_bytes=b_bytes)
    if store:
        _store_result(res)
    return res


def autotune_batched_gemm(
    g: int, m: int, k: int, n: int,
    in_bytes: int = 4,
    out_bytes: int = 4,
    shared: str = "none",
    spec: TpuSpec = TPU_V5E,
    *,
    num_shards: int = 1,
    axis: str | None = None,
    top_k: int = DEFAULT_TOP_K,
    repeats: int = DEFAULT_REPEATS,
    engine: str | None = None,
    max_elements: int = DEFAULT_MAX_ELEMENTS,
    store: bool = True,
) -> TuneResult:
    """Measured search for the batched/grouped GEMM family (same contract
    as ``autotune_gemm``; ``shared`` marks the 2-D cross-batch operand)."""
    engine = _check_engine(engine or default_engine())
    base_spec = spec                # see autotune_gemm: calibration basis
    spec = tuner.effective_spec(spec)
    if num_shards > 1:
        opts = tuner.batched_placement_options(
            g, m, k, n, num_shards, in_bytes, out_bytes, shared, spec, axis)
        return _tune_placed(
            "batched", (g, m, k, n), opts, in_bytes, out_bytes, spec,
            lambda dims: autotune_batched_gemm(
                *dims, in_bytes, out_bytes, shared, spec, top_k=top_k,
                repeats=repeats, engine=engine, max_elements=max_elements,
                store=False),
            num_shards=num_shards, engine=engine, store=store,
            extra=f"shared:{shared}")

    cands = tuner.batched_candidates(g, m, k, n, in_bytes, out_bytes, shared,
                                     spec)
    sl = tuner.shortlist(cands, top_k)
    gg, mm, kk, nn = _scale_batched(g, m, k, n, max_elements)
    in_dt, out_dt = _dtype(in_bytes), _dtype(out_bytes)
    a = _rand((mm, kk) if shared == "a" else (gg, mm, kk), in_dt)
    b = _rand((kk, nn) if shared == "b" else (gg, kk, nn), in_dt, seed=1)
    times, widx = _measure_shortlist(
        sl, lambda c: _batched_runner(engine, a, b, c, out_dt), repeats)
    winner = replace(sl[widx], mode="measured")
    est_meas = estimate_batched(
        gg, mm, kk, nn, bm=winner.bm, bn=winner.bn, bk=winner.bk,
        dim_order=winner.dim_order, shared_a=shared == "a",
        shared_b=shared == "b", in_bytes=in_bytes, out_bytes=out_bytes,
        edge=winner.edge, spec=base_spec)
    res = TuneResult(
        family="batched", dims=(g, m, k, n), measured_dims=(gg, mm, kk, nn),
        key=plan_store.shape_key("batched", (g, m, k, n), in_bytes,
                                 out_bytes, extra=f"shared:{shared}"),
        plan=winner, t_measured=times[widx], t_analytic=times[0],
        analytic_plan=sl[0], est_measured=est_meas, engine=engine,
        timed=tuple((c.bm, c.bn, c.bk, c.dim_order, t)
                    for c, t in zip(sl, times)))
    if store:
        _store_result(res)
    return res


def autotune_ragged_gemm(
    g: int, total: int, k: int, n: int,
    in_bytes: int = 4,
    out_bytes: int = 4,
    ragged: str = "m",
    spec: TpuSpec = TPU_V5E,
    *,
    num_shards: int = 1,
    axis: str | None = None,
    top_k: int = DEFAULT_TOP_K,
    repeats: int = DEFAULT_REPEATS,
    engine: str | None = None,
    max_elements: int = DEFAULT_MAX_ELEMENTS,
    store: bool = True,
    b_bytes: int | None = None,
) -> TuneResult:
    """Measured search for the ragged grouped GEMM family.  The harness
    times a *balanced* distribution of the same signature (per-group counts
    are dynamic at run time; the plan is keyed by the aggregate anyway).
    ``b_bytes`` searches the mixed-width axis (quantized expert panels
    against wide activations), keyed ``ragged:m+bb{n}``."""
    engine = _check_engine(engine or default_engine())
    base_spec = spec                # see autotune_gemm: calibration basis
    spec = tuner.effective_spec(spec)
    if num_shards > 1:
        opts = tuner.ragged_placement_options(
            g, total, k, n, num_shards, in_bytes, out_bytes, ragged, spec,
            axis)
        return _tune_placed(
            "ragged", (g, total, k, n), opts, in_bytes, out_bytes, spec,
            lambda dims: autotune_ragged_gemm(
                *dims, in_bytes, out_bytes, ragged, spec, top_k=top_k,
                repeats=repeats, engine=engine, max_elements=max_elements,
                store=False),
            num_shards=num_shards, engine=engine, store=store,
            extra=f"ragged:{ragged}")

    cands = tuner.ragged_candidates(g, total, k, n, in_bytes, out_bytes,
                                    ragged, spec, b_bytes=b_bytes)
    sl = tuner.shortlist(cands, top_k)
    gg, tt, kk, nn = _scale_ragged(g, total, k, n, max_elements)
    in_dt, out_dt = _dtype(in_bytes), _dtype(out_bytes)
    b_dt = in_dt if b_bytes is None else _dtype(b_bytes)
    offsets = _balanced_offsets(gg, tt)
    if ragged == "k":
        x = _rand((tt, kk), in_dt)           # (T, D)
        w = _rand((tt, nn), in_dt, seed=1)   # dy: (T, F)
    else:
        x = _rand((tt, kk), in_dt)
        w = _rand((gg, kk, nn), b_dt, seed=1)
    times, widx = _measure_shortlist(
        sl, lambda c: _ragged_runner(engine, x, w, offsets, c, out_dt,
                                     ragged), repeats)
    winner = replace(sl[widx], mode="measured")
    est_meas = estimate_ragged(gg, tt, kk, nn, bm=winner.bm, bn=winner.bn,
                               bk=winner.bk, ragged=ragged,
                               in_bytes=in_bytes, out_bytes=out_bytes,
                               spec=base_spec, b_bytes=b_bytes)
    res = TuneResult(
        family="ragged", dims=(g, total, k, n),
        measured_dims=(gg, tt, kk, nn),
        key=plan_store.shape_key(
            "ragged", (g, total, k, n), in_bytes, out_bytes,
            extra=tuner._dtype_extra(b_bytes, f"ragged:{ragged}")),
        plan=winner, t_measured=times[widx], t_analytic=times[0],
        analytic_plan=sl[0], est_measured=est_meas, engine=engine,
        timed=tuple((c.bm, c.bn, c.bk, "mn", t)
                    for c, t in zip(sl, times)),
        in_bytes=in_bytes, b_bytes=b_bytes)
    if store:
        _store_result(res)
    return res


def _placed_total(t_local: float, placement) -> float:
    """Compose a measured local time with the modeled collective exactly the
    way ``Plan.t_total`` does: SUM for the gather schedule, MAX for the ring
    (the overlapped transfer hides behind compute)."""
    if placement.schedule == "ring":
        return max(t_local * placement.waste, placement.t_collective)
    return t_local * placement.waste + placement.t_collective


def _tune_placed(family, dims, options, in_bytes, out_bytes, spec,
                 tune_local, *, num_shards, engine, store,
                 extra: str = "") -> TuneResult:
    """Hybrid placed search: measured local GEMM per ``PlacementOption``,
    modeled collective/waste terms (schedule-composed), the same clear-win
    margins as the analytic placer."""
    scored = []
    for opt in options:
        res = tune_local(opt.local_dims)
        total = _placed_total(res.t_measured, opt.placement)
        scored.append((opt, res, total))
    best_i = 0
    for i, (opt, _res, total) in enumerate(scored[1:], start=1):
        if total * opt.margin < scored[best_i][2]:
            best_i = i
    opt, local, total = scored[best_i]
    winner = replace(local.plan, placement=opt.placement, mode="measured")
    # The analytic placed choice, scored with ITS analytic blocks' measured
    # time — the apples-to-apples baseline for this harness run.
    analytic_scored = [
        (o, _placed_total(r.t_analytic, o.placement))
        for o, r, _t in scored]
    a_i = 0
    for i, (o, t) in enumerate(analytic_scored[1:], start=1):
        if t * o.margin < analytic_scored[a_i][1]:
            a_i = i
    a_opt, a_local, _ = scored[a_i]
    res = TuneResult(
        family=family, dims=dims, measured_dims=local.measured_dims,
        key=plan_store.shape_key(family, dims, in_bytes, out_bytes,
                                 num_shards=num_shards, extra=extra),
        plan=winner, t_measured=total, t_analytic=analytic_scored[a_i][1],
        analytic_plan=replace(a_local.analytic_plan,
                              placement=a_opt.placement),
        est_measured=local.est_measured, engine=engine, timed=local.timed)
    if store:
        _store_result(res, num_shards=num_shards,
                      strategy=opt.placement.strategy,
                      schedule=opt.placement.schedule)
    return res


# ---------------------------------------------------------------------------
# Calibration: fit the effective TpuSpec constants from (prediction,
# measurement) pairs so unmeasured shapes plan better too.
# ---------------------------------------------------------------------------

def prediction_error(samples, flops_frac: float = 1.0,
                     bw_frac: float = 1.0) -> float:
    """Geomean multiplicative error of the roofline prediction
    ``max(t_compute / flops_frac, t_memory / bw_frac)`` against measurement
    — 1.0 is a perfect model, symmetric in over/under-prediction."""
    logs = []
    for est, t_meas in samples:
        tp = max(est.t_compute / flops_frac, est.t_memory / bw_frac)
        logs.append(abs(math.log(max(tp, 1e-12) / max(t_meas, 1e-12))))
    return math.exp(sum(logs) / len(logs)) if logs else 1.0


def geomean_ratio(samples, flops_frac: float = 1.0,
                  bw_frac: float = 1.0) -> float:
    """Signed geomean of predicted/measured (shows the bias direction)."""
    logs = []
    for est, t_meas in samples:
        tp = max(est.t_compute / flops_frac, est.t_memory / bw_frac)
        logs.append(math.log(max(tp, 1e-12) / max(t_meas, 1e-12)))
    return math.exp(sum(logs) / len(logs)) if logs else 1.0


def fit_calibration(samples, *, engine: str = "",
                    spec: TpuSpec = TPU_V5E) -> Calibration:
    """Grid-fit (achievable-flops fraction, effective-bandwidth fraction)
    minimizing the geomean prediction error over ``samples`` — a list of
    (PlanEstimate-of-measured-problem, measured-seconds) pairs, e.g.
    ``[(r.est_measured, r.t_measured) for r in results]``.

    Coordinate grid in log space (the roofline max() makes the objective
    piecewise-smooth but not convex; the grid is cheap and global), then one
    refinement round around the coarse winner."""
    if not samples:
        return Calibration(engine=engine, base_spec=spec.name)

    def sweep(centers, span, steps):
        best = None
        for ef in range(-steps, steps + 1):
            ff = centers[0] * (10 ** (ef * span / steps))
            for eb in range(-steps, steps + 1):
                bf = centers[1] * (10 ** (eb * span / steps))
                err = prediction_error(samples, ff, bf)
                if best is None or err < best[0]:
                    best = (err, ff, bf)
        return best

    _, ff, bf = sweep((1.0, 1.0), span=4.0, steps=16)       # 1e-4 .. 1e4
    _, ff, bf = sweep((ff, bf), span=0.25, steps=8)         # refine
    return Calibration(flops_frac=ff, bw_frac=bf, n_samples=len(samples),
                       engine=engine, base_spec=spec.name)


def calibrate(results, *, spec: TpuSpec = TPU_V5E,
              store: bool = True) -> Calibration:
    """Fit calibration from a batch of ``TuneResult``s and (by default)
    install it in the plan store, where ``tuner.effective_spec`` picks it up
    for every subsequent default-spec planning decision.  (``est_measured``
    is always expressed in the raw base spec, so refitting with a
    calibration already installed composes correctly instead of collapsing
    to ~1.0.)

    Narrow-dtype results (``in_bytes == 1`` — the full-int8 compute path,
    whose predictions price against ``TpuSpec.peak_flops_int8``) are fitted
    SEPARATELY into ``flops_frac_int8``: the int8 MXU path saturates
    differently from the float path, so one shared fraction would misprice
    whichever family wasn't measured.  Mixed weight-only results
    (``b_bytes`` set, wide activations) compute on the float path and stay
    in the main fit."""
    engines = {r.engine for r in results}
    wide = [r for r in results if getattr(r, "in_bytes", 4) != 1]
    narrow = [r for r in results if getattr(r, "in_bytes", 4) == 1]
    cal = fit_calibration([(r.est_measured, r.t_measured) for r in wide],
                          engine=",".join(sorted(engines)), spec=spec)
    if narrow:
        # Fit the int8 flops fraction against the MAIN fit's bandwidth
        # fraction (the wires don't change with the MXU path); fall back to
        # a narrow-only joint fit when no wide samples anchored bw_frac.
        nsam = [(r.est_measured, r.t_measured) for r in narrow]
        if wide:
            best = None
            for e in range(-64, 65):
                ff = 10.0 ** (e * 4.0 / 64)
                err = prediction_error(nsam, ff, cal.bw_frac)
                if best is None or err < best[0]:
                    best = (err, ff)
            int8_frac = best[1]
        else:
            ncal = fit_calibration(nsam, engine=cal.engine, spec=spec)
            cal = replace(cal, bw_frac=ncal.bw_frac)
            int8_frac = ncal.flops_frac
        cal = replace(cal, flops_frac_int8=int8_frac,
                      n_samples=len(results))
    if store:
        st = plan_store.get_store()
        old = st.calibration
        if old is not None:          # keep a fitted ICI fraction, if any
            cal = replace(cal, ici_frac=old.ici_frac)
        st.kind = st.kind or plan_store.device_kind()
        st.calibration = cal
        tuner.clear_planner_caches()
    return cal


# ---------------------------------------------------------------------------
# Mesh-measured extensions: end-to-end placed timing + ICI calibration.
# Until this landed the placed search timed only the LOCAL GEMM and kept the
# ICI term modeled; these helpers time placed plans on the actual mesh
# (collectives executed, not priced) and fit the effective-ICI-bandwidth
# constant the same way ``calibrate`` fits flops/HBM.
# ---------------------------------------------------------------------------

def calibrate_ici(mesh, axis="data", *,
                  widths=(128, 256),
                  rows: int = 4096,
                  repeats: int = DEFAULT_REPEATS,
                  spec: TpuSpec = TPU_V5E,
                  store: bool = True) -> Calibration:
    """Fit the effective-ICI-bandwidth fraction from timed mesh exchanges.

    Times the EP exchange round-trip (``all_gather`` in, ``psum_scatter``
    back — the two legs ``cmr.estimate_ep`` prices) on ``mesh[axis]`` and
    fits ``ici_frac`` so the modeled exchange matches measurement:
    ``t_effective = t_model / ici_frac``, geomean over samples.  On fake
    host devices this absorbs the whole software-collective overhead — the
    point is that the *same* constant then corrects every planned
    ``t_collective``, exactly like the HBM-bandwidth fraction corrects
    ``t_memory``.  Installed into the store's ``Calibration`` (preserving
    fitted flops/HBM fractions) unless ``store=False``."""
    from ..compat import shard_map_unchecked
    from jax.sharding import PartitionSpec as P

    nc = int(mesh.shape[axis])
    cal_base = plan_store.get_store().calibration or Calibration(
        engine="ici", base_spec=spec.name)
    if nc <= 1:
        return cal_base
    logs = []
    for width in widths:
        r = max(nc, rows - rows % nc)
        x = _rand((r, width), jnp.float32)

        def roundtrip(x_l):
            full = jax.lax.all_gather(x_l, axis, axis=0, tiled=True)
            return jax.lax.psum_scatter(full, axis, scatter_dimension=0,
                                        tiled=True)

        fn = jax.jit(shard_map_unchecked(
            roundtrip, mesh=mesh, in_specs=(P(axis, None),),
            out_specs=P(axis, None)))
        t_meas = _ops.bench(lambda: fn(x), repeats=repeats)
        ex = estimate_ep(r, width, nc, elt_bytes=4, spec=spec)
        t_model = 2.0 * ex.t_exchange            # both legs
        logs.append(math.log(max(t_model, 1e-12) / max(t_meas, 1e-12)))
    ici = math.exp(sum(logs) / len(logs)) if logs else 1.0
    cal = replace(cal_base, ici_frac=ici,
                  n_samples=cal_base.n_samples + len(logs))
    if store:
        st = plan_store.get_store()
        st.kind = st.kind or plan_store.device_kind()
        st.calibration = cal
        tuner.clear_planner_caches()
    return cal


def time_placed_ragged_e2e(g: int, total: int, k: int, n: int, *,
                           mesh, axis="data",
                           in_bytes: int = 4, out_bytes: int = 4,
                           repeats: int = DEFAULT_REPEATS,
                           backend: str = "xla") -> list[dict]:
    """Time the placed ragged options END-TO-END on the mesh — collectives
    executed, not modeled — one row per (strategy, schedule) candidate plus
    the single-device reference:

      * ``single`` — the unplaced ragged GEMM (the m_parallel proxy: on a
        timeshared host mesh every shard shares one core, so the sharded
        m_parallel wall time equals the single-device wall time).
      * ``expert_parallel``/``gather`` and ``expert_parallel``/``ring`` —
        the real EP executors under each schedule.

    Each row carries ``t_measured`` (seconds) and the planner's modeled
    ``t_model`` for the matching option (``Plan.t_total`` under the current
    calibration), so callers can check the measured winner against the
    modeled winner — the crossover-agreement gate."""
    from .dispatch import ragged_matmul as _ragged
    from .distributed import ep_ragged_matmul as _ep

    nc = int(mesh.shape[axis]) if not isinstance(axis, (tuple, list)) \
        else int(math.prod(mesh.shape[a] for a in axis))
    in_dt, out_dt = _dtype(in_bytes), _dtype(out_bytes)
    x = _rand((total, k), in_dt)
    w = _rand((g, k, n), in_dt, seed=1)
    offsets = _balanced_offsets(g, total)

    rows: list[dict] = []
    f1 = jax.jit(lambda x, w, o: _ragged(x, w, o, out_dtype=out_dt,
                                         backend=backend))
    rows.append({
        "strategy": "single", "schedule": "gather",
        "t_measured": _ops.bench(lambda: f1(x, w, offsets),
                                 repeats=repeats),
        "t_model": tuner.plan_ragged_gemm(g, total, k, n, in_bytes,
                                          out_bytes).t_total,
    })
    opts = {(o.placement.strategy, o.placement.schedule): o
            for o in tuner.ragged_placement_options(
                g, total, k, n, nc, in_bytes, out_bytes, "m",
                tuner.effective_spec(TPU_V5E))}
    for schedule in ("gather", "ring"):
        fe = jax.jit(functools.partial(
            _ep, mesh=mesh, axis=axis, out_dtype=out_dt, backend=backend,
            schedule=schedule))
        opt = opts.get(("expert_parallel", schedule))
        t_model = float("nan")
        if opt is not None:
            plan = replace(opt.plan_local(in_bytes, out_bytes,
                                          tuner.effective_spec(TPU_V5E)),
                           placement=opt.placement)
            t_model = plan.t_total
        rows.append({
            "strategy": "expert_parallel", "schedule": schedule,
            "t_measured": _ops.bench(lambda: fe(x, w, offsets),
                                     repeats=repeats),
            "t_model": t_model,
        })
    return rows


def time_placed_dense_e2e(m: int, k: int, n: int, *, mesh, axis="model",
                          in_bytes: int = 4, out_bytes: int = 4,
                          repeats: int = DEFAULT_REPEATS,
                          backend: str = "xla") -> list[dict]:
    """Time the dense placed strategies end-to-end on the mesh through
    ``dist_matmul``: m_parallel, k_parallel/gather (psum) and
    k_parallel/ring (overlapped collective matmul), with the planner's
    modeled ``t_total`` alongside each."""
    from .distributed import dist_matmul as _dist

    nc = int(mesh.shape[axis])
    in_dt, out_dt = _dtype(in_bytes), _dtype(out_bytes)
    a = _rand((m, k), in_dt)
    b = _rand((k, n), in_dt, seed=1)
    opts = {(o.placement.strategy, o.placement.schedule): o
            for o in tuner.dense_placement_options(
                m, k, n, nc, in_bytes, out_bytes,
                tuner.effective_spec(TPU_V5E))}
    rows: list[dict] = []
    for strategy, schedule in (("m_parallel", "gather"),
                               ("k_parallel", "gather"),
                               ("k_parallel", "ring")):
        fn = jax.jit(functools.partial(
            _dist, mesh=mesh, axis=axis, strategy=strategy,
            schedule=schedule, out_dtype=out_dt, backend=backend))
        opt = opts.get((strategy, schedule))
        t_model = float("nan")
        if opt is not None:
            plan = replace(opt.plan_local(in_bytes, out_bytes,
                                          tuner.effective_spec(TPU_V5E)),
                           placement=opt.placement)
            t_model = plan.t_total
        rows.append({
            "strategy": strategy, "schedule": schedule,
            "t_measured": _ops.bench(lambda: fn(a, b), repeats=repeats),
            "t_model": t_model,
        })
    return rows


# ---------------------------------------------------------------------------
# Persistence entry points (thin veneers over plan_store that also
# invalidate the planner LRUs, so loads take effect immediately).
# ---------------------------------------------------------------------------

def load_plan_cache(path: str) -> int:
    """Adopt a persistent plan-cache file (0 entries for missing / corrupt /
    other-device files — graceful, never raises) and invalidate the planner
    LRUs so the next ``plan_*`` serves ``mode == "cached"`` plans."""
    n = plan_store.get_store().load(path)
    tuner.clear_planner_caches()
    return n


def save_plan_cache(path: str | None = None) -> str:
    return plan_store.get_store().save(path)


def clear_plan_store() -> None:
    """Forget all in-memory measured plans + calibration (the on-disk file
    is untouched) and invalidate the planner LRUs."""
    plan_store.reset_store()
    tuner.clear_planner_caches()
