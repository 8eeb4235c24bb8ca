"""Dynamic adjusting (paper §IV-C): choose parallelization strategy, block
sizes, AND mesh placement per GEMM shape, at trace time, from the CMR model.

The paper fixes initial block sizes from CMR + capacity, then adjusts them to
the actual matrix shape at run time, and picks M-parallel vs K-parallel from
the shape (K-parallel iff M and N are both small and K is large, because only
splitting K can occupy all 8 DSP cores).  Here that decision is one level of
a unified *plan hierarchy*:

  * every planner (``plan_gemm`` / ``plan_batched_gemm`` /
    ``plan_ragged_gemm``) returns a ``Plan`` whose single-core tiling
    (bm, bn, bk, dim_order) comes from ONE shared candidate enumeration
    (``gemm_candidates`` / ``batched_candidates`` / ``ragged_candidates``)
    scored with ``cmr.estimate*`` under the VMEM budget;
  * when asked to place the GEMM on a mesh (``num_shards > 1``), the same
    plan additionally carries a ``Placement`` — the cross-chip strategy
    (m_parallel / k_parallel / expert_parallel), the modeled ICI collective
    term (psum for K-parallel, the token all-to-all for expert-parallel via
    ``cmr.estimate_ep``) and the load-imbalance waste — so strategy x
    blocking is ONE joint auto-tuning decision, mirroring Eqs. 1-4's
    num_core terms at mesh scale;
  * plans are LRU-cached per shape signature — the paper's "dynamic
    adjusting" happens once per (shape, dtype, placement request) and is
    free afterwards.

Closing the paper's auto-tuning loop (pillar three), the analytic argmin is
no longer the last word: each planner first consults the **persistent
measured-plan store** (``plan_store`` — filled by ``autotune``'s on-device
search over the CMR-shortlisted candidates) and every plan carries a
``mode``:

    "analytic"  — CMR argmin, never validated against hardware;
    "measured"  — returned directly by ``autotune.autotune_*`` after timing
                  the shortlist on the device;
    "cached"    — served from the persistent store (a previous measured
                  winner), re-estimated and re-validated at lookup.

``plan_distributed`` survives as the dense-only compat view (``DistPlan``);
``tgemm_plan`` reproduces the TGEMM strawman the paper compares against: one
fixed micro-kernel/block configuration regardless of shape, with implicit
padding of N (its waste shows up in ``est.flops_padded`` / traffic).
"""
from __future__ import annotations

import collections
import functools
from dataclasses import dataclass, replace

from . import plan_store
from ...analysis import contracts
from .cmr import (TPU_V5E, EpEstimate, PlanEstimate, TpuSpec, cdiv, ceil_to,
                  device_spec, estimate, estimate_batched, estimate_ep,
                  estimate_ragged)
from .shapes import GemmClass, classify


@dataclass(frozen=True)
class Placement:
    """Where a plan runs on the mesh.  ``None`` placement = single device.

    ``strategy`` is the paper's parallelization mode lifted to the mesh:
    "m_parallel" (Alg. 4: shard rows, replicate panels, no steady-state
    collective), "k_parallel" (Alg. 5: shard the contraction, psum the fp32
    partials) or "expert_parallel" (shard the group/expert dim, all-to-all
    the tokens to their owning shard and back).  ``t_collective`` is the
    modeled ICI term of that choice, ``ici_bytes`` the global bytes it moves,
    and ``waste`` the load-imbalance multiplier on the local estimate.

    ``schedule`` is the overlap axis the ring collective matmul added:
    "gather" runs the collective then the local GEMM back-to-back (t_total
    SUMS the two), "ring" rotates chunks around the mesh so each hop's
    transfer overlaps the next chunk's compute (t_total takes the MAX — the
    mesh-level analogue of the paper's DMA/compute pipelining).
    """
    strategy: str                   # m_parallel | k_parallel | expert_parallel
    num_shards: int = 1
    axis: str | None = None         # mesh axis name (advisory; executors bind)
    t_collective: float = 0.0       # modeled ICI cost (s) per call
    ici_bytes: float = 0.0          # global bytes over ICI per call
    waste: float = 1.0              # >= 1: shard-imbalance multiplier
    schedule: str = "gather"        # gather (unoverlapped) | ring (overlapped)


class Plan:
    """Base of the unified plan hierarchy: a local CMR estimate (``est``)
    plus an optional ``Placement``.  ``t_total`` composes them the same way
    for every family: local time x imbalance waste + ICI collective for the
    gather schedule, max(local, ICI) for the ring schedule (the transfer
    hides behind compute — whichever dominates sets the clock).
    ``mode`` records which tuning loop produced the plan (analytic CMR
    argmin / measured on device / served from the persistent cache)."""

    est: PlanEstimate | None
    placement: Placement | None
    mode: str

    @property
    def t_total(self) -> float:
        t = self.est.t_total if self.est is not None else 0.0
        p = self.placement
        if p is not None:
            if p.schedule == "ring":
                t = max(t * p.waste, p.t_collective)
            else:
                t = t * p.waste + p.t_collective
        return t

    @property
    def strategy(self) -> str:
        return self.placement.strategy if self.placement is not None \
            else "single"


@dataclass(frozen=True)
class GemmPlan(Plan):
    bm: int
    bn: int
    bk: int
    nsplit: int = 1                 # in-kernel split-K factor
    dim_order: str = "mn"
    gemm_class: GemmClass = GemmClass.REGULAR
    est: PlanEstimate | None = None
    placement: Placement | None = None
    mode: str = "analytic"          # analytic | measured | cached
    edge: str = "masked"            # masked (zero-copy) | padded (pad/slice)
    fuse: bool = True               # fuse the requested epilogue in-kernel

    def kernel_kwargs(self) -> dict:
        return dict(bm=self.bm, bn=self.bn, bk=self.bk,
                    nsplit=self.nsplit, dim_order=self.dim_order,
                    edge=self.edge)


@dataclass(frozen=True)
class DistPlan(Plan):
    """Compat view of a placed dense plan (the paper's two cross-chip
    strategies).  ``local`` is the per-shard ``GemmPlan``; strategy/cost
    accessors read through to its ``Placement``."""
    local: GemmPlan
    placement: Placement
    est: PlanEstimate | None = None
    mode: str = "analytic"

    @property
    def num_cores(self) -> int:
        return self.placement.num_shards

    @property
    def t_collective(self) -> float:
        return self.placement.t_collective


def _bm_candidates(m: int, unit: int) -> list[int]:
    """Row-block candidates, multiples of ``unit``
    (``contracts.bm_unit``)."""
    cands = [c for c in (128, 256, 512, 1024) if c <= ceil_to(m, unit)]
    if m < 128:
        cands.append(ceil_to(m, unit))
    return sorted(set(cands)) or [ceil_to(m, unit)]


def _fits(family: str, bm: int, bn: int, bk: int, *, in_bytes: int,
          out_bytes: int, spec: TpuSpec, b_bytes: int | None = None,
          ragged: str = "m", epilogue=None, swiglu: bool = False) -> bool:
    """VMEM admission on the FULL per-step footprint — blocks, accumulator,
    and the epilogue / SwiGLU inputs the kernel really carries
    (``contracts.vmem_footprint``) — against the spec's budget, which keeps
    headroom under the limit the kernels pass to the compiler."""
    return contracts.vmem_footprint(
        family, bm=bm, bn=bn, bk=bk, in_bytes=in_bytes, out_bytes=out_bytes,
        ragged=ragged, epilogue=epilogue, swiglu=swiglu,
        b_bytes=b_bytes) <= spec.vmem_budget


def _bn_candidates(n: int, lane: int) -> list[int]:
    top = ceil_to(n, lane)
    cands = [c for c in (128, 256, 512) if c <= top]
    if top <= 1024:
        cands.append(top)
    return sorted(set(cands)) or [top]


def _bk_candidates(k: int) -> list[int]:
    top = ceil_to(k, 128)
    cands = [c for c in (128, 256, 512, 1024, 2048) if c <= top]
    if top <= 4096:
        cands.append(top)   # full-K residency — enables gk == 1 reuse
    return sorted(set(cands)) or [top]


def effective_spec(spec: TpuSpec) -> TpuSpec:
    """Swap the stock default spec for this device's spec
    (``cmr.device_spec``: looked up by TPU kind, v5e on other backends) and
    that for its measured calibration, when the persistent store carries
    one (``autotune.calibrate`` fits the achievable flops fraction +
    effective HBM bandwidth from measured-vs-predicted ratios).
    Explicitly-passed custom specs are honored untouched — the calibration
    corrects the *default* constants so shapes that were never measured
    still plan against reality."""
    if spec is not TPU_V5E:
        return spec
    spec = device_spec()
    cal = plan_store.get_store().calibration
    if cal is None:
        return spec
    return spec.calibrated(cal.flops_frac, cal.bw_frac,
                           getattr(cal, "ici_frac", 1.0),
                           int8_frac=getattr(cal, "flops_frac_int8", None))


# ---------------------------------------------------------------------------
# Shared candidate enumeration — ONE generator per plan family, used by both
# the analytic argmin below and autotune's measured shortlist.
# ---------------------------------------------------------------------------

def _edge_variants(m: int, k: int, n: int, bm: int, bn: int,
                   bk: int) -> tuple[str, ...]:
    """Edge policies worth enumerating for one blocking: ``padded`` only
    differs from ``masked`` (and only costs anything) when some dimension is
    not a block multiple."""
    if m % bm or n % bn or k % bk:
        return ("masked", "padded")
    return ("masked",)


def _fuse_variants(epi_ops: int) -> tuple[bool, ...]:
    return (True, False) if epi_ops > 0 else (True,)


def gemm_candidates(m: int, k: int, n: int, in_bytes: int = 4,
                    out_bytes: int = 4,
                    spec: TpuSpec = TPU_V5E,
                    epi_ops: int = 0, *, verify: bool = True,
                    b_bytes: int | None = None, trans: str = "nn",
                    epilogue=None, swiglu: bool = False
                    ) -> list[GemmPlan]:
    """Every VMEM-feasible candidate tiling for the dense GEMM, scored by
    the CMR model.  The candidate space is (blocking x dim order x edge
    policy x epilogue fusion): ``edge`` only forks on non-block-multiple
    shapes (where the padded wrapper pays real copies) and ``fuse`` only
    when the caller carries an epilogue (``epi_ops > 0``).  ``verify`` runs
    the static contract pre-check (``analysis.contracts.check_blocks``) so
    geometrically infeasible tilings are pruned BEFORE CMR pricing or
    measured timing.  ``trans`` sets the alignment unit of ``bm``
    (``contracts.bm_unit``); ``epilogue``/``swiglu`` describe the extra inputs the
    fused kernel carries, which VMEM admission counts (``_fits``).  Never
    empty: when nothing fits the budget the degenerate minimum tile is
    returned (and priced) as the only candidate."""
    cls = classify(m, k, n)
    unit = contracts.bm_unit(trans, in_bytes, spec)
    cands: list[GemmPlan] = []
    for bm in _bm_candidates(m, unit):
        for bn in _bn_candidates(n, spec.lane):
            for bk in _bk_candidates(k):
                for order in ("mn", "nm"):
                    if verify and contracts.errors(contracts.check_blocks(
                            "dense", (m, k, n), bm=bm, bn=bn, bk=bk,
                            dim_order=order, in_bytes=in_bytes,
                            out_bytes=out_bytes, trans=trans, spec=spec)):
                        continue
                    for edge in _edge_variants(m, k, n, bm, bn, bk):
                        for fuse in _fuse_variants(epi_ops):
                            if not _fits("dense", bm, bn, bk,
                                         in_bytes=in_bytes,
                                         out_bytes=out_bytes, spec=spec,
                                         b_bytes=b_bytes,
                                         epilogue=epilogue if fuse else None,
                                         swiglu=swiglu and fuse):
                                continue
                            e = estimate(m, k, n, bm=bm, bn=bn, bk=bk,
                                         dim_order=order, in_bytes=in_bytes,
                                         out_bytes=out_bytes, edge=edge,
                                         epi_ops=epi_ops, epi_fused=fuse,
                                         b_bytes=b_bytes, spec=spec)
                            cands.append(GemmPlan(
                                bm=bm, bn=bn, bk=bk, dim_order=order,
                                gemm_class=cls, est=e, edge=edge,
                                fuse=fuse))
    if not cands:   # degenerate: nothing fit; shrink to minimum tiles
        bm, bn, bk = min(128, ceil_to(m, unit)), 128, 128
        e = estimate(m, k, n, bm=bm, bn=bn, bk=bk, epi_ops=epi_ops,
                     in_bytes=in_bytes, out_bytes=out_bytes, b_bytes=b_bytes,
                     spec=spec)
        cands.append(GemmPlan(bm=bm, bn=bn, bk=bk, gemm_class=cls, est=e))
    return cands


def batched_candidates(g: int, m: int, k: int, n: int, in_bytes: int = 4,
                       out_bytes: int = 4, shared: str = "none",
                       spec: TpuSpec = TPU_V5E,
                       epi_ops: int = 0, *, verify: bool = True,
                       trans: str = "nn", epilogue=None,
                       swiglu: bool = False) -> list[GemmPlan]:
    """Candidate tilings for the batched/grouped GEMM (same enumeration as
    the dense family, including the edge-policy and epilogue-fusion forks,
    the ``trans``-dependent ``bm`` unit, the full-footprint VMEM admission
    and the same static contract pre-check; the batch-aware estimator
    decides whether a shared panel earns cross-batch residency)."""
    cls = classify(m, k, n)
    unit = contracts.bm_unit(trans, in_bytes, spec)
    shared_a, shared_b = shared == "a", shared == "b"
    cands: list[GemmPlan] = []
    for bm in _bm_candidates(m, unit):
        for bn in _bn_candidates(n, spec.lane):
            for bk in _bk_candidates(k):
                for order in ("mn", "nm"):
                    if verify and contracts.errors(contracts.check_blocks(
                            "batched", (g, m, k, n), bm=bm, bn=bn, bk=bk,
                            dim_order=order, in_bytes=in_bytes,
                            out_bytes=out_bytes, trans=trans, spec=spec)):
                        continue
                    for edge in _edge_variants(m, k, n, bm, bn, bk):
                        for fuse in _fuse_variants(epi_ops):
                            if not _fits("batched", bm, bn, bk,
                                         in_bytes=in_bytes,
                                         out_bytes=out_bytes, spec=spec,
                                         epilogue=epilogue if fuse else None,
                                         swiglu=swiglu and fuse):
                                continue
                            e = estimate_batched(
                                g, m, k, n, bm=bm, bn=bn, bk=bk,
                                dim_order=order, shared_a=shared_a,
                                shared_b=shared_b, in_bytes=in_bytes,
                                out_bytes=out_bytes, edge=edge,
                                epi_ops=epi_ops, epi_fused=fuse, spec=spec)
                            cands.append(GemmPlan(
                                bm=bm, bn=bn, bk=bk, dim_order=order,
                                gemm_class=cls, est=e, edge=edge,
                                fuse=fuse))
    if not cands:
        bm, bn, bk = min(128, ceil_to(m, unit)), 128, 128
        e = estimate_batched(g, m, k, n, bm=bm, bn=bn, bk=bk,
                             shared_a=shared_a, shared_b=shared_b,
                             in_bytes=in_bytes, out_bytes=out_bytes,
                             epi_ops=epi_ops, spec=spec)
        cands.append(GemmPlan(bm=bm, bn=bn, bk=bk, gemm_class=cls, est=e))
    return cands


def _ragged_tile_candidates(total: int, g: int, sublane: int) -> list[int]:
    """Row-tile candidates for the ragged dimension.

    Unlike the dense case, a smaller tile can win: every group boundary
    wastes at most one tile of padded compute, so tiles near the *mean*
    group size keep the boundary waste proportional to the distribution —
    the whole point of pricing off actual sizes instead of the max."""
    top = ceil_to(max(total, 1), sublane)
    mean = max(total // max(g, 1), 1)
    cands = {c for c in (64, 128, 256, 512) if c <= top}
    cands.add(min(ceil_to(mean, sublane), 512, top))
    if total < 64:
        cands.add(top)
    return sorted(cands)


def ragged_candidates(g: int, total: int, k: int, n: int, in_bytes: int = 4,
                      out_bytes: int = 4, ragged: str = "m",
                      spec: TpuSpec = TPU_V5E, *, verify: bool = True,
                      b_bytes: int | None = None, epilogue=None,
                      swiglu: bool = False) -> list[GemmPlan]:
    """Candidate tilings for the ragged grouped GEMM: the ragged dimension's
    tile list comes from the *distribution* (mean group size), the dense
    dimensions from the shared dense lists.  No dim_order choice — the
    ragged kernels fix their grid walk.  Same static contract pre-check as
    the dense enumeration."""
    sublane = spec.sublane(in_bytes)
    mean = max(total // max(g, 1), 1)
    if ragged == "m":
        cls = classify(mean, k, n)
        bms = _ragged_tile_candidates(total, g, sublane)
        bns, bks = _bn_candidates(n, spec.lane), _bk_candidates(k)
    elif ragged == "k":
        cls = classify(k, mean, n)
        bms = _bm_candidates(k, spec.lane)      # D: minor axis of x's block
        bns, bks = _bn_candidates(n, spec.lane), \
            _ragged_tile_candidates(total, g, sublane)
    else:
        raise ValueError(ragged)
    cands: list[GemmPlan] = []
    for bm in bms:
        for bn in bns:
            for bk in bks:
                if verify and contracts.errors(contracts.check_blocks(
                        "ragged", (g, total, k, n), bm=bm, bn=bn, bk=bk,
                        ragged=ragged, in_bytes=in_bytes,
                        out_bytes=out_bytes, spec=spec)):
                    continue
                if not _fits("ragged", bm, bn, bk, in_bytes=in_bytes,
                             out_bytes=out_bytes, spec=spec,
                             b_bytes=b_bytes, ragged=ragged,
                             epilogue=epilogue, swiglu=swiglu):
                    continue
                e = estimate_ragged(g, total, k, n, bm=bm, bn=bn, bk=bk,
                                    ragged=ragged, in_bytes=in_bytes,
                                    out_bytes=out_bytes, b_bytes=b_bytes,
                                    spec=spec)
                cands.append(GemmPlan(bm=bm, bn=bn, bk=bk, gemm_class=cls,
                                      est=e))
    if not cands:
        bm, bn, bk = min(128, ceil_to(max(total, 1), sublane)), 128, 128
        if ragged == "k":
            bm, bk = 128, min(128, ceil_to(max(total, 1), sublane))
        e = estimate_ragged(g, total, k, n, bm=bm, bn=bn, bk=bk,
                            ragged=ragged, in_bytes=in_bytes,
                            out_bytes=out_bytes, b_bytes=b_bytes, spec=spec)
        cands.append(GemmPlan(bm=bm, bn=bn, bk=bk, gemm_class=cls, est=e))
    return cands


def _better(a: GemmPlan, b: GemmPlan) -> bool:
    ta, tb = a.est.t_total, b.est.t_total
    if abs(ta - tb) > 0.02 * max(ta, tb):
        return ta < tb
    # Tie-break as the paper does: prefer larger bk (more accumulator reuse),
    # then smaller padding waste, then the zero-copy edge policy and the
    # fused epilogue (fewer HBM round-trips at equal modeled time).
    if a.bk != b.bk:
        return a.bk > b.bk
    if a.est.flops_padded != b.est.flops_padded:
        return a.est.flops_padded < b.est.flops_padded
    if a.edge != b.edge:
        return a.edge == "masked"
    return a.fuse and not b.fuse


def argmin_plan(cands: list[GemmPlan]) -> GemmPlan:
    """The analytic winner under the CMR model (with the paper's tie-break
    rules) over one candidate list."""
    best = cands[0]
    for cand in cands[1:]:
        if _better(cand, best):
            best = cand
    return best


def shortlist(cands: list[GemmPlan], top_k: int) -> list[GemmPlan]:
    """The model-pruned search space the measured auto-tuner times: the
    analytic argmin first (so measured mode can never lose to it on the same
    harness run), then the next-best candidates by modeled time."""
    best = argmin_plan(cands)
    ordered = [best] + sorted(
        (c for c in cands if c is not best),
        key=lambda c: (c.est.t_total, c.est.flops_padded))
    seen: set[tuple] = set()
    out: list[GemmPlan] = []
    for c in ordered:
        sig = (c.bm, c.bn, c.bk, c.nsplit, c.dim_order, c.edge, c.fuse)
        if sig in seen:
            continue
        seen.add(sig)
        out.append(c)
        if len(out) >= max(top_k, 1):
            break
    return out


# ---------------------------------------------------------------------------
# Persistent-store consultation: cached measured winners are re-estimated
# (fresh PlanEstimate at the requested spec) and re-validated — the cache
# can suggest a tiling, never force a shape-invalid one.
# ---------------------------------------------------------------------------

def _plan_from_record(rec: dict, estimator, cls: GemmClass,
                      spec: TpuSpec, admit=None) -> GemmPlan | None:
    """A cached record as a plan under ``spec``, or None when it is
    shape-invalid.  ``admit(bm, bn, bk, fuse)`` is the caller's geometry and
    full-footprint VMEM check; without one the base footprint of the fresh
    estimate is held to the budget."""
    try:
        bm, bn, bk = int(rec["bm"]), int(rec["bn"]), int(rec["bk"])
        nsplit = int(rec.get("nsplit", 1))
        order = str(rec.get("dim_order", "mn"))
        edge = str(rec.get("edge", "masked"))
        fuse = bool(rec.get("fuse", True))
    except (KeyError, TypeError, ValueError):
        return None
    if bm <= 0 or bn <= 0 or bk <= 0 or nsplit <= 0 \
            or order not in ("mn", "nm") or bn % spec.lane \
            or edge not in ("masked", "padded"):
        return None
    e = estimator(bm, bn, bk, order, edge)
    if e is None or e.vmem_bytes > spec.vmem_budget:
        return None
    if admit is not None and not admit(bm, bn, bk, fuse):
        return None
    return GemmPlan(bm=bm, bn=bn, bk=bk, nsplit=nsplit, dim_order=order,
                    gemm_class=cls, est=e, mode="cached", edge=edge,
                    fuse=fuse)


def _record_admit(family: str, dims: tuple, in_bytes: int, out_bytes: int,
                  spec: TpuSpec, *, trans: str = "nn", ragged: str = "m",
                  b_bytes: int | None = None, epilogue=None,
                  swiglu: bool = False):
    """``_plan_from_record``'s ``admit`` for one call: the cached tiling
    must pass the block contracts under ``trans`` and fit VMEM with the
    inputs its (fused) kernel carries."""
    def admit(bm: int, bn: int, bk: int, fuse: bool) -> bool:
        if contracts.errors(contracts.check_blocks(
                family, dims, bm=bm, bn=bn, bk=bk, in_bytes=in_bytes,
                out_bytes=out_bytes, ragged=ragged, trans=trans, spec=spec)):
            return False
        return _fits(family, bm, bn, bk, in_bytes=in_bytes,
                     out_bytes=out_bytes, spec=spec, b_bytes=b_bytes,
                     ragged=ragged, epilogue=epilogue if fuse else None,
                     swiglu=swiglu and fuse)
    return admit


def _dtype_extra(b_bytes: int | None, base: str = "") -> str:
    """The plan-store key fragment for a mixed-dtype B operand: ``"bb1"``
    joined onto any family variant with "+".  Homogeneous calls keep their
    legacy key (no fragment) so existing stores stay addressable."""
    if b_bytes is None:
        return base
    frag = f"bb{int(b_bytes)}"
    return f"{base}+{frag}" if base else frag


def _cached_dense(m, k, n, in_bytes, out_bytes, spec, b_bytes=None,
                  trans="nn", epilogue=None,
                  swiglu=False) -> GemmPlan | None:
    rec = plan_store.get_store().lookup(
        plan_store.shape_key("dense", (m, k, n), in_bytes, out_bytes,
                             extra=_dtype_extra(b_bytes)))
    if rec is None:
        return None

    def est(bm, bn, bk, order, edge="masked"):
        return estimate(m, k, n, bm=bm, bn=bn, bk=bk, nsplit=1,
                        dim_order=order, in_bytes=in_bytes,
                        out_bytes=out_bytes, edge=edge, b_bytes=b_bytes,
                        spec=spec)

    admit = _record_admit("dense", (m, k, n), in_bytes, out_bytes, spec,
                          trans=trans, b_bytes=b_bytes, epilogue=epilogue,
                          swiglu=swiglu)
    return _plan_from_record(rec, est, classify(m, k, n), spec, admit)


def _cached_batched(g, m, k, n, in_bytes, out_bytes, shared, spec,
                    trans="nn", epilogue=None,
                    swiglu=False) -> GemmPlan | None:
    rec = plan_store.get_store().lookup(
        plan_store.shape_key("batched", (g, m, k, n), in_bytes, out_bytes,
                             extra=f"shared:{shared}"))
    if rec is None:
        return None

    def est(bm, bn, bk, order, edge="masked"):
        return estimate_batched(g, m, k, n, bm=bm, bn=bn, bk=bk,
                                dim_order=order, shared_a=shared == "a",
                                shared_b=shared == "b", in_bytes=in_bytes,
                                out_bytes=out_bytes, edge=edge, spec=spec)

    admit = _record_admit("batched", (g, m, k, n), in_bytes, out_bytes, spec,
                          trans=trans, epilogue=epilogue, swiglu=swiglu)
    return _plan_from_record(rec, est, classify(m, k, n), spec, admit)


def _cached_ragged(g, total, k, n, in_bytes, out_bytes, ragged, spec,
                   b_bytes=None, epilogue=None,
                   swiglu=False) -> GemmPlan | None:
    rec = plan_store.get_store().lookup(
        plan_store.shape_key("ragged", (g, total, k, n), in_bytes, out_bytes,
                             extra=_dtype_extra(b_bytes,
                                                f"ragged:{ragged}")))
    if rec is None:
        return None
    mean = max(total // max(g, 1), 1)
    cls = classify(mean, k, n) if ragged == "m" else classify(k, mean, n)

    def est(bm, bn, bk, order, edge="masked"):
        if order != "mn":       # ragged kernels fix their grid walk
            return None
        return estimate_ragged(g, total, k, n, bm=bm, bn=bn, bk=bk,
                               ragged=ragged, in_bytes=in_bytes,
                               out_bytes=out_bytes, b_bytes=b_bytes,
                               spec=spec)

    admit = _record_admit("ragged", (g, total, k, n), in_bytes, out_bytes,
                          spec, ragged=ragged, b_bytes=b_bytes,
                          epilogue=epilogue, swiglu=swiglu)
    return _plan_from_record(rec, est, cls, spec, admit)


def _cached_placed(family: str, dims: tuple, in_bytes: int, out_bytes: int,
                   num_shards: int, options, spec: TpuSpec,
                   extra: str = ""):
    """Reconstruct a placed measured winner: find the stored strategy among
    the analytic placement options (which carry the modeled collective/waste
    terms) and re-validate the stored local tiling on that option's local
    shape."""
    rec = plan_store.get_store().lookup(
        plan_store.shape_key(family, dims, in_bytes, out_bytes,
                             num_shards=num_shards, extra=extra))
    if rec is None:
        return None
    for opt in options:
        if opt.placement.strategy != rec.get("strategy"):
            continue
        if opt.placement.schedule != rec.get("schedule", "gather"):
            continue
        local = opt.cached_local(rec, in_bytes, out_bytes, spec)
        if local is None:
            return None
        return replace(local, placement=opt.placement, mode="cached")
    return None


# ---------------------------------------------------------------------------
# Placement options — the cross-chip layouts each family chooses between,
# with their modeled ICI/waste terms.  Shared by the analytic placers, the
# cached-plan reconstruction above, and autotune's measured placement search.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PlacementOption:
    """One candidate cross-chip layout: the per-shard local problem
    (``local_dims`` in the family's positional order), the modeled
    ``Placement``, and the margin a challenger must beat the preferred
    (first, collective-free) option by — the paper's "clear modeled win"
    rule for accepting a reduction/exchange strategy."""
    family: str
    local_dims: tuple
    placement: Placement
    margin: float = 1.0
    extra: str = ""

    def plan_local(self, in_bytes: int, out_bytes: int,
                   spec: TpuSpec) -> GemmPlan:
        if self.family == "dense":
            return plan_gemm(*self.local_dims, in_bytes, out_bytes, spec)
        if self.family == "batched":
            return plan_batched_gemm(*self.local_dims, in_bytes, out_bytes,
                                     self.extra, spec)
        return plan_ragged_gemm(*self.local_dims, in_bytes, out_bytes,
                                self.extra, spec)

    def cached_local(self, rec: dict, in_bytes: int = 4, out_bytes: int = 4,
                     spec: TpuSpec = TPU_V5E) -> GemmPlan | None:
        """Re-validate a stored local tiling against this option's local
        shape (fresh estimate under ``spec``); None if shape-invalid."""
        if self.family == "dense":
            m, k, n = self.local_dims

            def est(bm, bn, bk, order, edge="masked"):
                return estimate(m, k, n, bm=bm, bn=bn, bk=bk,
                                dim_order=order, in_bytes=in_bytes,
                                out_bytes=out_bytes, edge=edge, spec=spec)

            return _plan_from_record(rec, est, classify(m, k, n), spec)
        if self.family == "batched":
            g, m, k, n = self.local_dims

            def est(bm, bn, bk, order, edge="masked"):
                return estimate_batched(
                    g, m, k, n, bm=bm, bn=bn, bk=bk, dim_order=order,
                    shared_a=self.extra == "a", shared_b=self.extra == "b",
                    in_bytes=in_bytes, out_bytes=out_bytes, edge=edge,
                    spec=spec)

            return _plan_from_record(rec, est, classify(m, k, n), spec)
        g, total, k, n = self.local_dims
        mean = max(total // max(g, 1), 1)
        cls = classify(mean, k, n) if self.extra == "m" \
            else classify(k, mean, n)

        def est(bm, bn, bk, order, edge="masked"):
            if order != "mn":
                return None
            return estimate_ragged(g, total, k, n, bm=bm, bn=bn, bk=bk,
                                   ragged=self.extra, in_bytes=in_bytes,
                                   out_bytes=out_bytes, spec=spec)

        return _plan_from_record(rec, est, cls, spec)


def dense_placement_options(m: int, k: int, n: int, nc: int,
                            in_bytes: int = 4, out_bytes: int = 4,
                            spec: TpuSpec = TPU_V5E,
                            axis: str | None = None) -> list[PlacementOption]:
    """M-parallel vs K-parallel across ``nc`` chips (paper Alg. 4 vs 5).

    M-parallel: shard M; B replicated; no steady-state collective but a load
    imbalance term when M doesn't fill the chips.  K-parallel: shard K;
    partial C's reduced — a ring all-reduce of the fp32 partials over ICI —
    so it must win by a clear modeled margin (paper §IV-C: K-parallel
    "brings additional overhead of reduction").  K-parallel is offered under
    both schedules: "gather" (compute then psum, times SUM) and "ring" (the
    overlapped collective matmul: output chunks rotate while the next
    chunk's partial is computed — same bytes on the wire, but hidden behind
    compute, so times compose as MAX)."""
    sublane = spec.sublane(in_bytes)
    m_local = ceil_to(max(cdiv(m, nc), 1), sublane)
    waste_m = (cdiv(m, nc) * nc) / max(m, 1)
    opts = [PlacementOption(
        "dense", (m_local, k, n),
        Placement("m_parallel", nc, axis=axis, waste=waste_m))]

    k_local = ceil_to(max(cdiv(k, nc), 1), 128)
    ring = 2.0 * (nc - 1) / nc
    t_red = ring * (m * n * 4) / (spec.ici_bw_per_link * spec.ici_links)
    for schedule in ("ring", "gather"):
        opts.append(PlacementOption(
            "dense", (m, k_local, n),
            Placement("k_parallel", nc, axis=axis, t_collective=t_red,
                      ici_bytes=ring * m * n * 4 * nc, schedule=schedule),
            margin=1.15))
    return opts


def batched_placement_options(g: int, m: int, k: int, n: int, nc: int,
                              in_bytes: int = 4, out_bytes: int = 4,
                              shared: str = "none", spec: TpuSpec = TPU_V5E,
                              axis: str | None = None) -> list[PlacementOption]:
    """Per-entry m_parallel (rows sharded, every shard streams all G panels)
    vs expert_parallel (the G dim sharded, tokens all-to-all'd to their
    owning shard and back, priced by ``estimate_ep``); EP must amortize its
    exchange before it displaces the collective-free layout."""
    sublane = spec.sublane(in_bytes)
    m_l = ceil_to(max(cdiv(m, nc), 1), sublane)
    waste_m = (cdiv(m, nc) * nc) / max(m, 1)
    opts = [PlacementOption(
        "batched", (g, m_l, k, n),
        Placement("m_parallel", nc, axis=axis, waste=waste_m),
        extra=shared)]

    g_l = max(cdiv(g, nc), 1)
    ex = estimate_ep(g * m, k, nc, elt_bytes=in_bytes, spec=spec) \
        + estimate_ep(g * m, n, nc, elt_bytes=out_bytes, spec=spec)
    waste_g = (g_l * nc) / max(g, 1)
    opts.append(PlacementOption(
        "batched", (g_l, m, k, n),
        Placement("expert_parallel", nc, axis=axis,
                  t_collective=ex.t_exchange, ici_bytes=ex.ici_bytes,
                  waste=waste_g),
        margin=1.1, extra=shared))
    return opts


def ragged_placement_options(g: int, total: int, k: int, n: int, nc: int,
                             in_bytes: int = 4, out_bytes: int = 4,
                             ragged: str = "m", spec: TpuSpec = TPU_V5E,
                             axis: str | None = None) -> list[PlacementOption]:
    """Token-parallel (rows sharded, weights replicated) vs expert-parallel
    (groups sharded + the two token-exchange legs), with EP offered under
    both schedules.  EP "ring" is the overlapped collective matmul: token
    blocks rotate around the mesh and each shard computes only the blocks
    intersecting its owned window, so per-shard compute is ~2 block-spans of
    owned rows (priced as ``min(total, 2 * t_l)`` local rows) and the
    rotation bytes hide behind it (MAX composition).  EP "gather" is the
    unoverlapped exchange + ONE local GEMM over the worst-case window —
    every row could route to this shard's experts, so its local estimate
    honestly prices the FULL ``total`` rows (the old mean-rows pricing
    predicted a 3.65x EP win where measurement showed a 4.8x loss).  The EP
    backward dW (``ragged == "k"``) contracts rows that already live on the
    owning shard after the forward exchange — expert-local, no collective,
    no alternative."""
    t_l = max(cdiv(total, nc), 1)
    g_l = max(cdiv(g, nc), 1)
    waste = (cdiv(total, nc) * nc) / max(total, 1)
    if ragged == "k":
        return [PlacementOption(
            "ragged", (g_l, t_l, k, n),
            Placement("expert_parallel", nc, axis=axis, waste=waste),
            extra="k")]
    opts = [PlacementOption(
        "ragged", (g, t_l, k, n),
        Placement("m_parallel", nc, axis=axis, waste=waste), extra="m")]
    # Ring: (nc-1) x-block hops + nc output-block hops per shard.
    per_shard = ((nc - 1) * t_l * k * in_bytes
                 + nc * t_l * n * out_bytes)
    t_ring = per_shard / (spec.ici_bw_per_link * spec.ici_links)
    opts.append(PlacementOption(
        "ragged", (g_l, min(total, 2 * t_l), k, n),
        Placement("expert_parallel", nc, axis=axis, t_collective=t_ring,
                  ici_bytes=float(per_shard) * nc, waste=waste,
                  schedule="ring"),
        margin=1.1, extra="m"))
    ex = estimate_ep(total, k, nc, elt_bytes=in_bytes, spec=spec) \
        + estimate_ep(total, n, nc, elt_bytes=out_bytes, spec=spec)
    opts.append(PlacementOption(
        "ragged", (g_l, total, k, n),
        Placement("expert_parallel", nc, axis=axis,
                  t_collective=ex.t_exchange, ici_bytes=ex.ici_bytes,
                  waste=waste),
        margin=1.1, extra="m"))
    return opts


def _select_placed(scored: list[tuple[PlacementOption, GemmPlan]]) -> GemmPlan:
    """Pick among placed candidates: the first (collective-free) option is
    preferred; a challenger must beat it by its margin (the paper's "clear
    modeled win" rule, shared with autotune's measured placement search)."""
    best = scored[0][1]
    for opt, cand in scored[1:]:
        if cand.t_total * opt.margin < best.t_total:
            best = cand
    return best


@functools.lru_cache(maxsize=4096)
def preferred_ep_schedule(
    g: int, total: int, k: int, n: int,
    in_bytes: int = 4, out_bytes: int = 4,
    num_shards: int = 1,
    spec: TpuSpec = TPU_V5E,
    serial: int = 1,
) -> str:
    """Which EP exchange schedule the model prefers for this ragged shape:
    "ring" (overlapped) or "gather" (unoverlapped).  This is the planner
    knob the EP executors consult when the caller doesn't force a schedule
    (``REPRO_EP_SCHEDULE`` / explicit kwarg override it).

    ``serial`` multiplies the LOCAL term of every option: on a real mesh
    it is 1 (each shard has its own chip), but on a timeshared host mesh
    (fake devices forced onto one CPU) the shards' local GEMMs serialize,
    so wall-clock prediction needs the per-chip local time scaled by the
    shard count.  The executors pass ``serial=nc`` on the CPU backend —
    which is exactly why the gather schedule's worst-case-full-window
    compute loses there (the measured 4.8x EP slowdown) while the ring's
    owned-rows-only compute wins."""
    if num_shards <= 1:
        return "gather"
    spec = effective_spec(spec)
    best_t, best_s = float("inf"), "gather"
    for o in ragged_placement_options(g, total, k, n, num_shards, in_bytes,
                                      out_bytes, "m", spec):
        if o.placement.strategy != "expert_parallel":
            continue
        local = o.plan_local(in_bytes, out_bytes, spec).est.t_total \
            * o.placement.waste * max(1, serial)
        if o.placement.schedule == "ring":
            t = max(local, o.placement.t_collective)
        else:
            t = local + o.placement.t_collective
        if t < best_t:
            best_t, best_s = t, o.placement.schedule
    return best_s


# ---------------------------------------------------------------------------
# Planners
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=8192)
def plan_gemm(
    m: int, k: int, n: int,
    in_bytes: int = 4,
    out_bytes: int = 4,
    spec: TpuSpec = TPU_V5E,
    *,
    num_shards: int = 1,
    axis: str | None = None,
    epi_ops: int = 0,
    b_bytes: int | None = None,
    trans: str = "nn",
    epilogue=None,
    swiglu: bool = False,
) -> GemmPlan:
    """Pick the best tiling for C(M,N) += A(M,K) B(K,N) — and, when
    ``num_shards > 1``, the cross-chip strategy too: the returned plan is the
    per-shard tiling of the winning layout with its ``Placement`` attached
    (m_parallel vs k_parallel, scored with the psum ICI term).  Consults the
    persistent measured-plan store first (``mode == "cached"``); otherwise
    falls back to the analytic CMR argmin.

    ``epi_ops > 0`` declares a post-GEMM elementwise tail of that many ops
    (``Epilogue.num_ops``): the candidate space then forks on fusing it into
    the accumulator flush vs running it as separate passes, and the winner's
    ``fuse`` records the decision (alongside ``edge``, the masked-vs-padded
    remainder-tile policy).

    ``b_bytes`` is the dtype axis of the plan key: the B (weight) operand's
    element width when it differs from A's — the weight-only quantized GEMMs
    (int8/int4-unpacked weights against bf16/fp32 activations) — so traffic,
    VMEM and the achievable peak are priced per dtype combination and cached
    winners never leak across widths (the key carries a ``bb{n}`` extra).

    ``trans`` aligns ``bm`` to the lane tile where M is A's minor axis
    ("tn"); ``epilogue`` (the ``Epilogue`` the fused kernel carries) and
    ``swiglu`` (the fused gate/up pair: a second panel and accumulator)
    make VMEM admission count those inputs too."""
    spec = effective_spec(spec)
    if num_shards > 1:
        opts = dense_placement_options(m, k, n, num_shards, in_bytes,
                                       out_bytes, spec, axis)
        cached = _cached_placed("dense", (m, k, n), in_bytes, out_bytes,
                                num_shards, opts, spec)
        if cached is not None:
            return cached
        scored = [(o, replace(o.plan_local(in_bytes, out_bytes, spec),
                              placement=o.placement)) for o in opts]
        return _select_placed(scored)
    cached = _cached_dense(m, k, n, in_bytes, out_bytes, spec, b_bytes,
                           trans, epilogue, swiglu)
    if cached is not None:
        return cached
    return argmin_plan(gemm_candidates(m, k, n, in_bytes, out_bytes, spec,
                                       epi_ops, b_bytes=b_bytes, trans=trans,
                                       epilogue=epilogue, swiglu=swiglu))


@functools.lru_cache(maxsize=8192)
def plan_distributed(
    m: int, k: int, n: int,
    num_cores: int,
    in_bytes: int = 4,
    out_bytes: int = 4,
    spec: TpuSpec = TPU_V5E,
) -> DistPlan:
    """Choose M-parallel vs K-parallel across ``num_cores`` chips (the
    dense-only compat entry point; ``plan_gemm(..., num_shards=n)`` is the
    unified spelling and returns the same placed plan).  Unlike plan_gemm —
    whose num_shards=1 means "unplaced" — a degenerate single-core request
    still gets an (m_parallel, 1 shard, no collective) placement here, so
    ``.strategy`` / ``.num_cores`` always read."""
    spec = effective_spec(spec)
    nc = max(num_cores, 1)
    opts = dense_placement_options(m, k, n, nc, in_bytes, out_bytes, spec,
                                   None)
    cached = _cached_placed("dense", (m, k, n), in_bytes, out_bytes, nc, opts,
                            spec)
    if cached is not None:
        return DistPlan(local=cached, placement=cached.placement,
                        est=cached.est, mode="cached")
    scored = [(o, replace(o.plan_local(in_bytes, out_bytes, spec),
                          placement=o.placement)) for o in opts]
    p = _select_placed(scored)
    return DistPlan(local=p, placement=p.placement, est=p.est, mode=p.mode)


@functools.lru_cache(maxsize=8192)
def plan_batched_gemm(
    g: int, m: int, k: int, n: int,
    in_bytes: int = 4,
    out_bytes: int = 4,
    shared: str = "none",            # "none" | "a" | "b"
    spec: TpuSpec = TPU_V5E,
    *,
    num_shards: int = 1,
    axis: str | None = None,
    epi_ops: int = 0,
    trans: str = "nn",
    epilogue=None,
    swiglu: bool = False,
) -> GemmPlan:
    """Pick the best tiling for the batched GEMM C(g) += A(g) B(g).

    ``shared`` marks a 2-D operand reused by every batch entry (the grouped
    case); the batch-aware CMR model then credits cross-batch residency when
    the tiling actually earns it (single resident block), mirroring the
    paper's loop-order-for-reuse analysis with the batch as the outermost
    loop.  The per-entry shape is classified with the 2-D taxonomy (each MoE
    expert GEMM is T3/T1 per shard regardless of E).

    ``num_shards > 1``: place the batched GEMM on the mesh — per-entry
    m_parallel (rows sharded, every shard streams all G panels) vs
    expert_parallel (the G dim sharded, tokens all-to-all'd to their owning
    shard and back, priced by ``estimate_ep``).  ``trans`` / ``epilogue``
    / ``swiglu`` as in ``plan_gemm``."""
    spec = effective_spec(spec)
    if num_shards > 1:
        opts = batched_placement_options(g, m, k, n, num_shards, in_bytes,
                                         out_bytes, shared, spec, axis)
        cached = _cached_placed("batched", (g, m, k, n), in_bytes, out_bytes,
                                num_shards, opts, spec,
                                extra=f"shared:{shared}")
        if cached is not None:
            return cached
        scored = [(o, replace(o.plan_local(in_bytes, out_bytes, spec),
                              placement=o.placement)) for o in opts]
        return _select_placed(scored)
    cached = _cached_batched(g, m, k, n, in_bytes, out_bytes, shared, spec,
                             trans, epilogue, swiglu)
    if cached is not None:
        return cached
    return argmin_plan(batched_candidates(g, m, k, n, in_bytes, out_bytes,
                                          shared, spec, epi_ops, trans=trans,
                                          epilogue=epilogue, swiglu=swiglu))


@functools.lru_cache(maxsize=8192)
def plan_ragged_gemm(
    g: int, total: int, k: int, n: int,
    in_bytes: int = 4,
    out_bytes: int = 4,
    ragged: str = "m",
    spec: TpuSpec = TPU_V5E,
    *,
    num_shards: int = 1,
    axis: str | None = None,
    b_bytes: int | None = None,
    epilogue=None,
    swiglu: bool = False,
) -> GemmPlan:
    """Pick the best tiling for a ragged grouped GEMM over G groups.

    The cache key (g, total, k, n, dtype widths, ragged, placement request)
    is the *distribution signature*: per-group counts are dynamic (traced)
    so the plan prices the aggregate — total ragged rows plus one boundary
    tile per group — and is re-used by every call whose signature matches
    (one tuning per MoE layer shape, free afterwards, exactly like the
    paper's dynamic adjusting).

    ``ragged == "m"``: forward — (total, k) rows against per-group (k, n)
    panels; ``bm`` tiles the ragged rows.  ``ragged == "k"``: backward dW —
    the ragged dimension contracts (T2 per group); ``bk`` tiles it, ``k`` is
    the output panel's row dim.  The per-group *mean* shape is classified
    with the 2-D taxonomy (a balanced MoE dispatch is T3/T1 per expert).

    ``num_shards > 1``: place the ragged GEMM on the mesh — token-parallel
    (rows sharded, weights replicated: no collective but every shard streams
    all G panels) vs expert-parallel (groups sharded: only G/num_shards
    panels per shard, paid for with the two all-to-all token-exchange legs
    priced by ``estimate_ep``).  EP wins exactly when the panel-traffic
    saving amortizes the exchange — few tokens against many/large expert
    panels, the MoE decode regime.  ``epilogue`` / ``swiglu`` as in
    ``plan_gemm``.
    """
    spec = effective_spec(spec)
    if num_shards > 1:
        opts = ragged_placement_options(g, total, k, n, num_shards, in_bytes,
                                        out_bytes, ragged, spec, axis)
        cached = _cached_placed("ragged", (g, total, k, n), in_bytes,
                                out_bytes, num_shards, opts, spec,
                                extra=f"ragged:{ragged}")
        if cached is not None:
            return cached
        scored = [(o, replace(o.plan_local(in_bytes, out_bytes, spec),
                              placement=o.placement)) for o in opts]
        return _select_placed(scored)
    cached = _cached_ragged(g, total, k, n, in_bytes, out_bytes, ragged, spec,
                            b_bytes, epilogue, swiglu)
    if cached is not None:
        return cached
    return argmin_plan(ragged_candidates(g, total, k, n, in_bytes, out_bytes,
                                         ragged, spec, b_bytes=b_bytes,
                                         epilogue=epilogue, swiglu=swiglu))


@dataclass(frozen=True)
class MoeDispatchPlan(Plan):
    """Dispatch-mode x placement pricing for one MoE layer shape.

    ``rows`` is the effective expert-GEMM row count the dispatch mode
    produces: E x capacity for "capacity" (every expert padded to the max,
    overflow dropped), T x top_k for "ragged" (every routed copy, nothing
    else).  The roofline prices the layer's GEMM flops/bytes off ``rows``
    and its EP exchange off ``placement`` — ONE source of truth instead of
    per-consumer special cases."""
    rows: int
    est: PlanEstimate | None = None
    placement: Placement | None = None
    mode: str = "analytic"


@functools.lru_cache(maxsize=8192)
def plan_moe_dispatch(
    t: int, e: int, top_k: int, d_model: int, d_ff: int,
    *,
    dispatch: str = "capacity",
    capacity_factor: float = 1.25,
    elt_bytes: int = 2,
    num_shards: int = 1,
    axis: str | None = None,
    spec: TpuSpec = TPU_V5E,
) -> MoeDispatchPlan:
    """Price one MoE layer's dispatch mode + expert placement.

    ``num_shards > 1`` attaches the expert-parallel ``Placement`` with the
    two all-to-all legs of the FUSED pipeline (``ep_ragged_moe``): tokens
    out and back in d_model width, priced by ``estimate_ep`` — the d_ff-wide
    hidden is produced and consumed on the shard owning the expert and
    never crosses the axis.  (``d_ff`` stays in the signature/cache key: it
    sizes the layer's GEMMs for the rows-based pricing consumers.)"""
    spec = effective_spec(spec)
    if dispatch == "ragged":
        rows = t * top_k
    elif dispatch == "capacity":
        s = spec.sublane(elt_bytes)
        c = int(t * top_k * capacity_factor / e)
        rows = e * max(s, ceil_to(c, s))
    else:
        raise ValueError(f"unknown moe dispatch: {dispatch}")
    placement = None
    if num_shards > 1:
        leg = estimate_ep(rows, d_model, num_shards,
                          elt_bytes=elt_bytes, spec=spec)
        ex: EpEstimate = leg + leg            # dispatch + return
        placement = Placement("expert_parallel", num_shards, axis=axis,
                              t_collective=ex.t_exchange,
                              ici_bytes=ex.ici_bytes)
    return MoeDispatchPlan(rows=rows, placement=placement)


def tgemm_plan(m: int, k: int, n: int,
               in_bytes: int = 4, out_bytes: int = 4,
               spec: TpuSpec = TPU_V5E) -> GemmPlan:
    """The TGEMM baseline (paper Alg. 1): ONE fixed blocking for all shapes —
    (m_g=512, k_g=512, n_a=96, m_s=6) on FT-m7032; the TPU analogue keeps a
    fixed regular-GEMM tile (256, 256, 256) and pads everything into it."""
    bm, bn, bk = 256, 256, 256
    e = estimate(m, k, n, bm=bm, bn=bn, bk=bk,
                 in_bytes=in_bytes, out_bytes=out_bytes, spec=spec)
    return GemmPlan(bm=bm, bn=bn, bk=bk, gemm_class=classify(m, k, n), est=e)


# ---------------------------------------------------------------------------
# Plan-mode telemetry: dispatch and the mesh executors report which tuning
# loop (analytic / measured / cached) served each planned GEMM they trace.
# ---------------------------------------------------------------------------

PLAN_MODE_COUNTS: collections.Counter = collections.Counter()
EPILOGUE_COUNTS: collections.Counter = collections.Counter()
DEGRADED_COUNTS: collections.Counter = collections.Counter()


def note_plan_use(family: str, plan: Plan) -> None:
    """Executors call this when a plan reaches an execution path (trace
    time).  Keyed (family, mode) so ``plan_mode_stats`` shows whether the
    workload is being served by measurements or by the unvalidated model."""
    PLAN_MODE_COUNTS[(family, getattr(plan, "mode", "analytic"))] += 1


def note_epilogue(family: str, fused: bool) -> None:
    """Executors call this when they serve a GEMM that CARRIES an epilogue
    (identity epilogues don't count): ``fused`` means the elementwise tail
    ran in the same kernel/jit as the GEMM (the accumulator-flush fusion or
    the single-jit XLA fallback), not as separate output passes."""
    EPILOGUE_COUNTS[(family, "fused" if fused else "separate")] += 1


def note_degraded(family: str, rung: str) -> None:
    """Executors call this when a fallback-ladder rung serves a GEMM the
    primary engine failed on (kernel launch failure, collective failure,
    contract-violating plan).  Keyed (family, rung) — e.g. ``("dense",
    "pallas->xla")`` or ``("ep", "ring->gather")`` — so ``plan_mode_stats``
    surfaces degraded servings next to the plan modes and serve ``health()``
    can report degraded mode."""
    DEGRADED_COUNTS[(family, rung)] += 1


def degraded_stats() -> dict[str, int]:
    """{"family:rung": count} census of fallback-ladder servings (empty ==
    every planned GEMM ran on its primary engine)."""
    return {f"{family}:{rung}": count
            for (family, rung), count in sorted(DEGRADED_COUNTS.items())}


def epilogue_stats() -> dict[str, dict[str, int]]:
    """{family: {"fused"|"separate": count}} census of epilogue servings."""
    out: dict[str, dict[str, int]] = {}
    for (family, kind), count in sorted(EPILOGUE_COUNTS.items()):
        out.setdefault(family, {})[kind] = count
    return out


def plan_mode_stats() -> dict[str, dict[str, int]]:
    """{family: {mode: count}} census of plans that reached executors.  When
    any epilogue-carrying GEMMs were served, an extra ``"epilogue"`` entry
    reports fused-vs-separate coverage (``epilogue_stats`` aggregated) so
    serve warmup can print fusion coverage alongside the plan modes.
    Cached records the static verifier quarantined at load time show up as
    a per-family ``"quarantined"`` count — those shapes silently fell back
    to analytic planning, which this makes visible."""
    out: dict[str, dict[str, int]] = {}
    for (family, mode), count in sorted(PLAN_MODE_COUNTS.items()):
        out.setdefault(family, {})[mode] = count
    for key in plan_store.get_store().quarantined:
        family = key.split("|", 1)[0]
        fam = out.setdefault(family, {})
        fam["quarantined"] = fam.get("quarantined", 0) + 1
    epi: dict[str, int] = {}
    for (_family, kind), count in EPILOGUE_COUNTS.items():
        epi[kind] = epi.get(kind, 0) + count
    if epi:
        out["epilogue"] = dict(sorted(epi.items()))
    if DEGRADED_COUNTS:
        # Degraded servings: how many GEMMs a fallback-ladder rung served
        # after the primary engine failed (chaos-injected or real).
        out["degraded"] = degraded_stats()
    return out


def clear_plan_cache() -> None:
    """Reset EVERY plan-serving layer from one entry point: the five planner
    LRUs, the in-memory persistent store view, the mode-telemetry counters,
    the dispatch-level custom-VJP caches, and the bounded mesh-executor
    caches in ``distributed`` — executors close over planner state when they
    trace, so leaving them alive across a spec/cache reset serves stale
    plans (the bug this replaces: only the five LRUs were cleared)."""
    plan_gemm.cache_clear()
    plan_batched_gemm.cache_clear()
    plan_ragged_gemm.cache_clear()
    plan_distributed.cache_clear()
    plan_moe_dispatch.cache_clear()
    preferred_ep_schedule.cache_clear()
    PLAN_MODE_COUNTS.clear()
    EPILOGUE_COUNTS.clear()
    DEGRADED_COUNTS.clear()
    plan_store.reset_store()
    # Executor layers import the tuner; import them lazily to avoid cycles.
    from . import dispatch, distributed
    dispatch.clear_dispatch_caches()
    distributed.clear_executor_caches()


def clear_planner_caches() -> None:
    """Invalidate only the five planner LRUs — the minimal reset after the
    persistent store gains entries/calibration (``autotune`` calls this so
    the next ``plan_*`` consults the updated store; executors stay warm
    because their traced plans are re-planned per shape signature)."""
    plan_gemm.cache_clear()
    plan_batched_gemm.cache_clear()
    plan_ragged_gemm.cache_clear()
    plan_distributed.cache_clear()
    plan_moe_dispatch.cache_clear()
    preferred_ep_schedule.cache_clear()
