"""Static kernel-contract verifier: prove plans safe before any kernel runs.

Every generated ftIMM variant and every cached ``Plan`` is checked against
machine-checkable contracts WITHOUT executing a kernel:

  1. VMEM/footprint budget — the per-grid-step working set (double-buffered
     A/B blocks, fp32 accumulator, double-buffered output block, plus
     bias/residual/swiglu extra inputs and split-K fp32 partials) computed
     from block shapes and dtypes, rejected when it exceeds the device spec.
  2. Grid coverage & write-race analysis — the kernel's real output
     ``BlockSpec`` index map is evaluated symbolically over a sampled cdiv
     grid to prove every output block is stored by exactly one parallel grid
     point and that stores are invariant to the reduction dimension.  The
     ragged kernels' masked boundary-tile read-modify-write is the one
     *ordered* exception: it is sound only under the sorted visit list, which
     ``check_ragged_visits`` re-proves from the concrete metadata.
  3. Edge-mask soundness — masked-edge kernels must mask the contraction
     remainder on BOTH operands (the 0*NaN hazard), established by AST
     inspection of the kernel bodies; padded-edge plans must have their pad
     copies priced by the CMR estimate they carry.
  4. Plan invariants — block sizes clamped to problem extents (the PR 5
     bk-clamp bug class), sublane/lane alignment per dtype, split-K with a
     fused nonlinear epilogue is illegal, placement divisibility (EP expert
     counts, k_parallel K-shards).

Layering: this module imports NOTHING from ``repro`` at module level (stdlib
only) so ``core.gemm.tuner`` and ``core.gemm.plan_store`` can import it
without creating a cycle; the device spec, the CMR estimator, the kernel
index maps and the ragged metadata generator are pulled in lazily inside the
checks that need them.
"""
from __future__ import annotations

import ast
import inspect
import itertools
import textwrap
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Sequence

FAMILIES = ("dense", "batched", "ragged")
STRATEGIES = ("m_parallel", "k_parallel", "expert_parallel")
SCHEDULES = ("gather", "ring")
# Ring (overlapped) schedules exist only where a chunk rotation is defined:
# the dense k_parallel collective matmul and the ragged EP token pipeline.
_RING_LEGAL = {("dense", "k_parallel"), ("ragged", "expert_parallel")}
_EDGES = ("masked", "padded")
_ORDERS = ("mn", "nm")


def _ceil_to(x: int, b: int) -> int:
    return -(-x // b) * b


def _cdiv(x: int, b: int) -> int:
    return -(-x // b)


def _spec(spec: Any) -> Any:
    """Resolve the device spec (duck-typed: needs ``.vmem_budget``, ``.lane``
    and ``.sublane(dtype_bytes)``); defaults to the CMR TPU v5e model."""
    if spec is not None:
        return spec
    from ..core.gemm.cmr import TPU_V5E
    return TPU_V5E


@dataclass(frozen=True)
class Violation:
    """One broken contract.  ``severity == "error"`` means the plan must not
    run; warnings are report-only (surfaced by the sweep, never fatal)."""
    code: str
    message: str
    severity: str = "error"

    def __str__(self) -> str:
        return f"[{self.severity}] {self.code}: {self.message}"


class ContractError(AssertionError):
    """Raised by ``assert_plan`` (the ``REPRO_VERIFY=1`` dispatch mode)."""

    def __init__(self, violations: Sequence[Violation],
                 context: str = "") -> None:
        self.violations = tuple(violations)
        head = f"kernel contract violated for {context}: " if context else \
            "kernel contract violated: "
        super().__init__(head + "; ".join(str(v) for v in self.violations))


def errors(violations: Iterable[Violation]) -> list[Violation]:
    """Only the fatal subset."""
    return [v for v in violations if v.severity == "error"]


def block_aligned(dims: Sequence[int], blocks: Sequence[int]) -> bool:
    """True when every extent is an exact multiple of its block — the edge is
    degenerate and pad/slice copies are pure waste (zero-copy legal)."""
    return all(d % b == 0 for d, b in zip(dims, blocks))


# ---------------------------------------------------------------------------
# Contract 4: plan invariants (alignment, clamping, schedule legality)
# ---------------------------------------------------------------------------

def _check_dim(out: list[Violation], name: str, blk: int, extent: int,
               unit: int, unit_name: str) -> None:
    if blk % unit:
        out.append(Violation(
            "misaligned_block",
            f"{name}={blk} is not a multiple of the {unit_name} ({unit})"))
    if blk > _ceil_to(max(extent, 1), unit):
        out.append(Violation(
            "unclamped_block",
            f"{name}={blk} exceeds the problem extent {extent} rounded to "
            f"{_ceil_to(max(extent, 1), unit)} — the grid would pad "
            f"{name}-fold (the PR 5 bk-clamp bug class)"))


def bm_unit(trans: str, in_bytes: int, spec: Any = None) -> int:
    """Alignment unit of ``bm``: the sublane tile where M is the row axis of
    A's ``(bm, bk)`` block, the lane tile where it is the minor axis of the
    transposed ``(bk, bm)`` block (``trans == "tn"``)."""
    sp = _spec(spec)
    return sp.lane if trans == "tn" else sp.sublane(in_bytes)


def _check_bm(out: list[Violation], bm: int, m: int, trans: str,
              in_bytes: int, spec: Any) -> None:
    _check_dim(out, "bm", bm, m, bm_unit(trans, in_bytes, spec),
               "lane" if trans == "tn" else "sublane")


def check_blocks(family: str, dims: Sequence[int], *, bm: int, bn: int,
                 bk: int, nsplit: int = 1, dim_order: str = "mn",
                 edge: str = "masked", in_bytes: int = 4, out_bytes: int = 4,
                 ragged: str = "m", trans: str = "nn",
                 spec: Any = None) -> list[Violation]:
    """Pure-geometry plan invariants: positivity, alignment per dtype,
    clamping to problem extents, split-K factor sanity.  Cheap enough to run
    on every candidate the tuner generates, before CMR pricing.

    ``bm`` follows the lane rule wherever M is the minor axis of the A
    block: ``trans == "tn"`` (dense and batched, A block ``(bk, bm)``) and
    the ragged dW (``ragged == "k"``, x block ``(bk, bm)``)."""
    sp = _spec(spec)
    v: list[Violation] = []
    if min(bm, bn, bk) <= 0 or nsplit <= 0:
        v.append(Violation("nonpositive_block",
                           f"bm={bm} bn={bn} bk={bk} nsplit={nsplit} must "
                           "all be positive"))
        return v
    if edge not in _EDGES:
        v.append(Violation("bad_edge", f"edge={edge!r} not in {_EDGES}"))
    sub = sp.sublane(in_bytes)
    lane = sp.lane
    if family == "dense":
        if len(dims) != 3:
            return v + [Violation("bad_dims", f"dense wants (m, k, n), got "
                                              f"{tuple(dims)}")]
        m, k, n = dims
        if dim_order not in _ORDERS:
            v.append(Violation("bad_dim_order",
                               f"dim_order={dim_order!r} not in {_ORDERS}"))
        _check_bm(v, bm, m, trans, in_bytes, sp)
        _check_dim(v, "bn", bn, n, lane, "lane")
        _check_dim(v, "bk", bk, k, lane, "lane")
        if nsplit > 1 and nsplit > _cdiv(_ceil_to(max(k, 1), lane), bk):
            v.append(Violation(
                "unclamped_nsplit",
                f"nsplit={nsplit} exceeds the {_cdiv(_ceil_to(max(k, 1), lane), bk)} "
                f"K-blocks available at bk={bk} — some splits would be empty"))
    elif family == "batched":
        if len(dims) != 4:
            return v + [Violation("bad_dims", f"batched wants (g, m, k, n), "
                                              f"got {tuple(dims)}")]
        g, m, k, n = dims
        if g <= 0:
            v.append(Violation("nonpositive_block", f"batch g={g} must be "
                                                    "positive"))
        if dim_order not in _ORDERS:
            v.append(Violation("bad_dim_order",
                               f"dim_order={dim_order!r} not in {_ORDERS}"))
        _check_bm(v, bm, m, trans, in_bytes, sp)
        _check_dim(v, "bn", bn, n, lane, "lane")
        _check_dim(v, "bk", bk, k, lane, "lane")
        if nsplit != 1:
            v.append(Violation("splitk_unsupported",
                               "batched kernels have no split-K schedule"))
    elif family == "ragged":
        if len(dims) != 4:
            return v + [Violation("bad_dims", f"ragged wants (g, total, k, n),"
                                              f" got {tuple(dims)}")]
        g, total, k, n = dims
        if g <= 0:
            v.append(Violation("nonpositive_block", f"group count g={g} must "
                                                    "be positive"))
        if dim_order != "mn":
            v.append(Violation("bad_dim_order",
                               "ragged kernels walk a fixed visit order; only "
                               f"dim_order='mn' is defined (got {dim_order!r})"))
        if nsplit != 1:
            v.append(Violation("splitk_unsupported",
                               "ragged kernels have no split-K schedule"))
        if ragged == "m":
            # bm tiles the ragged token axis; bk/bn tile dense K/N.
            _check_dim(v, "bm", bm, total, sub, "sublane")
            _check_dim(v, "bk", bk, k, lane, "lane")
            _check_dim(v, "bn", bn, n, lane, "lane")
        elif ragged == "k":
            # dW layout: bk tiles the ragged token (contraction) axis, bm
            # tiles the D rows of the (g, D, F) output — D is the minor axis
            # of the (bk, bm) x block, so bm is lane-aligned.
            _check_dim(v, "bk", bk, total, sub, "sublane")
            _check_dim(v, "bm", bm, k, lane, "lane")
            _check_dim(v, "bn", bn, n, lane, "lane")
        else:
            v.append(Violation("bad_ragged_axis",
                               f"ragged axis {ragged!r} not in ('m', 'k')"))
    else:
        v.append(Violation("bad_family", f"family {family!r} not in "
                                         f"{FAMILIES}"))
    return v


def vmem_footprint(family: str, *, bm: int, bn: int, bk: int,
                   in_bytes: int = 4, out_bytes: int = 4, nsplit: int = 1,
                   ragged: str = "m", epilogue: Any = None,
                   swiglu: bool = False, b_bytes: int | None = None) -> int:
    """Per-grid-step VMEM working set in bytes: double-buffered A/B input
    blocks, the fp32 accumulator scratch, and the double-buffered output
    block (fp32 when split-K writes partials).  ``epilogue``/``swiglu`` add
    the extra kernel inputs the base CMR formula does not price: a bias row,
    a residual block, the scale vector, the second weight panel + second
    accumulator.  ``b_bytes`` is the B-operand element width when it differs
    from A's (the mixed-dtype weight-only paths: int8/int4 weights against
    bf16/fp32 activations)."""
    bb = in_bytes if b_bytes is None else b_bytes
    if family == "ragged" and ragged == "k":
        a_blk, b_blk = bk * bm, bk * bn   # x^T panel and dy panel
    else:
        a_blk, b_blk = bm * bk, bk * bn
    out_elt = 4 if nsplit > 1 else out_bytes
    total = (2 * a_blk * in_bytes + 2 * b_blk * bb
             + bm * bn * 4 + 2 * bm * bn * out_elt)
    if swiglu:
        # Second weight panel (double-buffered) + second fp32 accumulator.
        total += 2 * b_blk * bb + bm * bn * 4
    if epilogue is not None:
        if getattr(epilogue, "bias", False):
            total += 2 * bn * out_bytes
        if getattr(epilogue, "residual", False):
            total += 2 * bm * bn * out_bytes
        if getattr(epilogue, "scale_vec", False):
            total += 2 * bn * 4         # fp32 dequant vector row
    return total


def check_schedule(*, nsplit: int = 1, fuse: bool = True, epilogue: Any = None,
                   swiglu: bool = False) -> list[Violation]:
    """Split-K ∧ nonlinear-epilogue legality.  A nonlinear tail (activation /
    swiglu gate) fused into the per-split flush would apply the nonlinearity
    to PARTIAL sums — act(a+b) != act(a)+act(b) — so a split-K plan may only
    claim ``fuse`` for tails applied after the cross-split reduction."""
    v: list[Violation] = []
    if nsplit <= 1:
        return v
    nonlinear = swiglu or (
        epilogue is not None
        and getattr(epilogue, "activation", "none") != "none")
    if fuse and nonlinear:
        v.append(Violation(
            "splitk_nonlinear_epilogue",
            f"nsplit={nsplit} with a fused nonlinear epilogue would apply "
            "the activation to partial sums"))
    # NOTE: a scale_vec epilogue (the quantized paths' dequant) is LINEAR —
    # it commutes with the cross-split sum, so split-K legally applies it
    # post-reduction and no violation is raised for it here.
    if swiglu:
        v.append(Violation("splitk_unsupported",
                           "no split-K swiglu kernel exists"))
    return v


def check_epilogue_vectors(family: str, dims: Sequence[int], epilogue: Any,
                           *, bias_shape: Sequence[int] | None = None,
                           scale_shape: Sequence[int] | None = None
                           ) -> list[Violation]:
    """Scale-vector / per-expert-bias operand legality for one planned call.

    The flush-time vector operands must be (N,)-wide — broadcast over rows —
    or, for the grouped/ragged families, (G, N) per-expert panels indexed by
    the visit list's group id.  A wrong N silently broadcasts or raises deep
    inside pallas; checking it here turns it into a named contract."""
    v: list[Violation] = []
    if epilogue is None:
        return v
    n = int(dims[-1])
    g = int(dims[0]) if family in ("batched", "ragged") else None

    def _check(name: str, flag: bool, shape) -> None:
        if not flag or shape is None:
            return
        shp = tuple(int(s) for s in shape)
        ok = shp == (n,) or (g is not None and shp == (g, n))
        if not ok:
            want = f"({n},)" if g is None else f"({n},) or ({g}, {n})"
            v.append(Violation(
                f"bad_{name}_shape",
                f"{family} epilogue {name} operand has shape {shp}; "
                f"expected {want}"))

    _check("scale", getattr(epilogue, "scale_vec", False), scale_shape)
    _check("bias", getattr(epilogue, "bias", False), bias_shape)
    return v


def check_placement(family: str, dims: Sequence[int], placement: Any,
                    spec: Any = None) -> list[Violation]:
    """Placement divisibility: EP needs the expert/group count divisible by
    the shard count (mirrors ``launch.sharding.expert_axis``); k_parallel
    must leave every shard at least one 128-wide K panel; the ring
    (overlapped) schedule only exists where a chunk rotation is defined."""
    sp = _spec(spec)
    v: list[Violation] = []
    strategy = getattr(placement, "strategy", None)
    nshards = int(getattr(placement, "num_shards", 1))
    if strategy not in STRATEGIES:
        return [Violation("bad_strategy",
                          f"placement strategy {strategy!r} not in "
                          f"{STRATEGIES}")]
    if nshards < 1:
        return [Violation("bad_shards", f"num_shards={nshards} must be >= 1")]
    schedule = getattr(placement, "schedule", "gather")
    if schedule not in SCHEDULES:
        return [Violation("bad_schedule",
                          f"placement schedule {schedule!r} not in "
                          f"{SCHEDULES}")]
    if schedule == "ring" and (family, strategy) not in _RING_LEGAL:
        v.append(Violation(
            "ring_undefined",
            f"ring schedule is undefined for ({family}, {strategy}); legal "
            f"pairs: {sorted(_RING_LEGAL)}"))
    if strategy == "expert_parallel":
        if family not in ("batched", "ragged"):
            v.append(Violation("strategy_family",
                               f"expert_parallel is undefined for {family}"))
        else:
            g = int(dims[0])
            if g % nshards:
                v.append(Violation(
                    "ep_indivisible",
                    f"{g} experts over {nshards} shards leaves ragged expert "
                    "placement; launch.sharding.expert_axis refuses this"))
    elif strategy == "k_parallel":
        if family != "dense":
            v.append(Violation("strategy_family",
                               f"k_parallel is undefined for {family}"))
        else:
            k = int(dims[1])
            if nshards > _cdiv(max(k, 1), sp.lane):
                v.append(Violation(
                    "kparallel_overshard",
                    f"{nshards} K-shards over K={k} leaves shards without a "
                    f"full {sp.lane}-wide panel", severity="warning"))
    return v


# ---------------------------------------------------------------------------
# Contract 2: grid coverage & write-race analysis
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class KernelContract:
    """What a generated variant promises about its output stores.

    ``out_index_map`` is the kernel's REAL output BlockSpec index map (taken
    from ``kernels.ftimm.kernel``, not re-derived), evaluated over sampled
    grid points.  ``ordered_rmw`` marks the ragged masked read-modify-write,
    which is exempt from the exactly-once rule but must instead satisfy the
    sorted-visit-list contract (``check_ragged_visits``)."""
    name: str
    grid: tuple[int, ...]
    out_extent: tuple[int, ...]
    out_index_map: Callable[..., tuple[int, ...]]
    store_dims: tuple[int, ...]
    reduction_dims: tuple[int, ...]
    needs_k_mask: bool
    ordered_rmw: bool = False


def _samples(extent: int, cap: int) -> list[int]:
    """Boundary-biased sample of a grid dimension: the first ``cap`` points
    plus the last one (edge tiles live there)."""
    return sorted(set(range(min(extent, cap))) | {extent - 1})


def variant_contract(family: str, dims: Sequence[int], plan: Any, *,
                     trans: str = "nn", swiglu: bool = False
                     ) -> KernelContract:
    """Build the store contract for a generated dense/batched variant from
    the kernel module's actual BlockSpecs."""
    from ..kernels.ftimm import kernel as _kernel
    bm, bn, bk = int(plan.bm), int(plan.bn), int(plan.bk)
    nsplit = int(getattr(plan, "nsplit", 1))
    order = getattr(plan, "dim_order", "mn")
    if family == "dense":
        m, k, n = dims
        gm, gn, gk = _cdiv(m, bm), _cdiv(n, bn), _cdiv(k, bk)
        if nsplit > 1:
            # Split-K grid (nsplit, gm, gn, gk_per_split); the partials
            # output is (nsplit, gm, gn) blocks, indexed (s, i, j).
            gks = _cdiv(gk, nsplit)
            return KernelContract(
                name="ftimm_gemm_splitk",
                grid=(nsplit, gm, gn, gks),
                out_extent=(nsplit, gm, gn),
                out_index_map=lambda s, i, j, kb: (s, i, j),
                store_dims=(0, 1, 2), reduction_dims=(3,),
                needs_k_mask=bool(k % bk) or bool(gk % nsplit))
        c_spec = _kernel._specs(trans, bm, bn, bk, order)[2]
        grid = (gm, gn, gk) if order == "mn" else (gn, gm, gk)
        return KernelContract(
            name="ftimm_gemm_swiglu" if swiglu else "ftimm_gemm",
            grid=grid, out_extent=(gm, gn),
            out_index_map=c_spec.index_map,
            store_dims=(0, 1), reduction_dims=(2,),
            needs_k_mask=bool(k % bk))
    if family == "batched":
        g, m, k, n = dims
        gm, gn, gk = _cdiv(m, bm), _cdiv(n, bn), _cdiv(k, bk)
        c_spec = _kernel._batched_specs(trans, bm, bn, bk, order,
                                        a_batched=True, b_batched=True)[2]
        grid = (g, gm, gn, gk) if order == "mn" else (g, gn, gm, gk)
        return KernelContract(
            name="ftimm_gemm_grouped_swiglu" if swiglu else "ftimm_gemm_batched",
            grid=grid, out_extent=(g, gm, gn),
            out_index_map=c_spec.index_map,
            store_dims=(0, 1, 2), reduction_dims=(3,),
            needs_k_mask=bool(k % bk))
    raise ValueError(f"no static store contract for family {family!r} "
                     "(ragged is the ordered exception: check_ragged_visits)")


def verify_contract(contract: KernelContract, cap: int = 3
                    ) -> list[Violation]:
    """Symbolically evaluate the output index map over a boundary-biased
    sample of the grid: stores must be invariant to the reduction dims, land
    in range, collide on no two parallel grid points, and cover every sampled
    output block."""
    v: list[Violation] = []
    seen_codes: set[str] = set()

    def flag(code: str, msg: str) -> None:
        if code not in seen_codes:
            seen_codes.add(code)
            v.append(Violation(code, f"{contract.name}: {msg}"))

    store_samples = [_samples(contract.grid[d], cap)
                     for d in contract.store_dims]
    red_samples = [sorted({0, contract.grid[d] - 1})
                   for d in contract.reduction_dims]
    produced: dict[tuple[int, ...], tuple[int, ...]] = {}
    for pt in itertools.product(*store_samples):
        outs = set()
        for red in itertools.product(*red_samples):
            coords = [0] * len(contract.grid)
            for d, val in zip(contract.store_dims, pt):
                coords[d] = val
            for d, val in zip(contract.reduction_dims, red):
                coords[d] = val
            outs.add(tuple(int(x) for x in contract.out_index_map(*coords)))
        if len(outs) > 1:
            flag("store_moves_with_reduction",
                 f"store target varies over the reduction dim at grid point "
                 f"{pt}: {sorted(outs)}")
            continue
        idx = next(iter(outs))
        if len(idx) != len(contract.out_extent) or any(
                not 0 <= x < e for x, e in zip(idx, contract.out_extent)):
            flag("out_of_range_store",
                 f"grid point {pt} stores block {idx}, outside extent "
                 f"{contract.out_extent}")
            continue
        if idx in produced and not contract.ordered_rmw:
            flag("write_race",
                 f"grid points {produced[idx]} and {pt} both store output "
                 f"block {idx} — last-writer-wins is schedule-dependent")
        produced[idx] = pt
    expected = set(itertools.product(
        *(_samples(e, cap) for e in contract.out_extent)))
    missing = expected - set(produced)
    if missing:
        flag("coverage_gap",
             f"{len(missing)} sampled output blocks are never stored, e.g. "
             f"{sorted(missing)[:4]}")
    return v


# ---------------------------------------------------------------------------
# Contract 3: edge-mask soundness (AST inspection, no execution)
# ---------------------------------------------------------------------------

def masked_operand_count(fn: Callable[..., Any]) -> int:
    """How many distinct operands a kernel body routes through
    ``_mask_contract`` — the masked-edge kernels must mask the contraction
    remainder on EVERY operand of the dot (zeroing one side still multiplies
    the other side's garbage: 0 * NaN == NaN).  Counted from the AST."""
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return -1
    masked: set[str] = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Call):
            f = node.func
            name = f.id if isinstance(f, ast.Name) else getattr(f, "attr", "")
            if name == "_mask_contract" and node.args:
                arg = node.args[0]
                masked.add(arg.id if isinstance(arg, ast.Name)
                           else ast.dump(arg))
    return len(masked)


def _calls(fn: Callable[..., Any], callee: str) -> bool:
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return False
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Call):
            f = node.func
            name = f.id if isinstance(f, ast.Name) else getattr(f, "attr", "")
            if name == callee:
                return True
    return False


def check_contraction_masking(accum_body: Callable[..., Any] | None = None,
                              swiglu_body: Callable[..., Any] | None = None,
                              dw_kernel: Callable[..., Any] | None = None
                              ) -> list[Violation]:
    """Prove (by AST) that every masked-edge kernel body masks all operands
    of its contraction: 2 for the dense/batched accumulate body (A and B),
    3 for the swiglu body (x, w_gate, w_up), and the ragged dW kernel must
    mask invalid token rows on its input side (``_ragged_row_mask``)."""
    if accum_body is None or swiglu_body is None or dw_kernel is None:
        from ..kernels.ftimm import kernel as _kernel
        accum_body = accum_body or _kernel._accum_body
        swiglu_body = swiglu_body or _kernel._swiglu_body
        dw_kernel = dw_kernel or _kernel._ragged_dw_kernel
    v: list[Violation] = []
    n = masked_operand_count(accum_body)
    if 0 <= n < 2:
        v.append(Violation(
            "missing_k_mask",
            f"dense accumulate body masks only {n} operand(s) of the "
            "contraction remainder; both A and B must be masked (0*NaN)"))
    n = masked_operand_count(swiglu_body)
    if 0 <= n < 3:
        v.append(Violation(
            "missing_k_mask",
            f"swiglu body masks only {n} operand(s); x, w_gate and w_up must "
            "all be masked"))
    if not _calls(dw_kernel, "_ragged_row_mask"):
        v.append(Violation(
            "missing_input_mask",
            "ragged dW kernel does not mask invalid token rows "
            "(_ragged_row_mask) — padded tokens would leak into dW"))
    return v


def _pad_priced(family: str, dims: Sequence[int], plan: Any, *,
                in_bytes: int, out_bytes: int, spec: Any,
                b_bytes: int | None = None) -> list[Violation]:
    """Padded-edge plans must carry a CMR estimate whose HBM traffic includes
    the pad round-trip copies (``cmr._pad_copy_bytes``)."""
    est = getattr(plan, "est", None)
    if est is None or getattr(est, "hbm_bytes", None) is None:
        return []
    bm, bn, bk = int(plan.bm), int(plan.bn), int(plan.bk)
    from ..core.gemm import cmr
    if family == "dense":
        m, k, n = dims
        if block_aligned((m, k, n), (bm, bk, bn)):
            return []
        floor = cmr.estimate(m, k, n, bm=bm, bn=bn, bk=bk,
                             nsplit=int(getattr(plan, "nsplit", 1)),
                             dim_order=getattr(plan, "dim_order", "mn"),
                             in_bytes=in_bytes, out_bytes=out_bytes,
                             spec=_spec(spec), edge="padded",
                             b_bytes=b_bytes).hbm_bytes
    elif family == "batched":
        g, m, k, n = dims
        if block_aligned((m, k, n), (bm, bk, bn)):
            return []
        floor = cmr.estimate_batched(g, m, k, n, bm=bm, bn=bn, bk=bk,
                                     dim_order=getattr(plan, "dim_order",
                                                       "mn"),
                                     in_bytes=in_bytes, out_bytes=out_bytes,
                                     spec=_spec(spec), edge="padded"
                                     ).hbm_bytes
    else:
        return []
    if est.hbm_bytes < floor - 0.5:
        return [Violation(
            "pad_copies_unpriced",
            f"padded-edge plan prices {est.hbm_bytes:.3g} HBM bytes but the "
            f"pad round-trip floor is {floor:.3g} — the tuner would compare "
            "it against masked plans with an unfair cost")]
    return []


# ---------------------------------------------------------------------------
# The umbrella check
# ---------------------------------------------------------------------------

def check_plan(family: str, dims: Sequence[int], plan: Any, *,
               in_bytes: int = 4, out_bytes: int = 4, spec: Any = None,
               epilogue: Any = None, swiglu: bool = False, ragged: str = "m",
               trans: str = "nn", coverage: bool = False,
               b_bytes: int | None = None) -> list[Violation]:
    """Check one plan (a ``tuner.GemmPlan``/``BatchedPlan``/``RaggedPlan`` or
    anything duck-typed like one) against every static contract.  With
    ``coverage=True`` the dense/batched store contract is also symbolically
    verified from the kernel's real index maps.  ``b_bytes`` declares a
    mixed-dtype B operand (the weight-only quantized paths) so the VMEM
    working set prices the narrow weight panel honestly."""
    sp = _spec(spec)
    bm = getattr(plan, "bm", None)
    v: list[Violation] = []
    if bm is not None:
        nsplit = int(getattr(plan, "nsplit", 1))
        v += check_blocks(family, dims, bm=int(plan.bm), bn=int(plan.bn),
                          bk=int(plan.bk), nsplit=nsplit,
                          dim_order=getattr(plan, "dim_order", "mn"),
                          edge=getattr(plan, "edge", "masked"),
                          in_bytes=in_bytes, out_bytes=out_bytes,
                          ragged=ragged, trans=trans, spec=sp)
        base = vmem_footprint(family, bm=int(plan.bm), bn=int(plan.bn),
                              bk=int(plan.bk), in_bytes=in_bytes,
                              out_bytes=out_bytes, nsplit=nsplit,
                              ragged=ragged, b_bytes=b_bytes)
        if base > sp.vmem_budget:
            v.append(Violation(
                "vmem_budget",
                f"per-step working set {base} B exceeds the "
                f"{sp.vmem_budget} B VMEM budget"))
        else:
            full = vmem_footprint(family, bm=int(plan.bm), bn=int(plan.bn),
                                  bk=int(plan.bk), in_bytes=in_bytes,
                                  out_bytes=out_bytes, nsplit=nsplit,
                                  ragged=ragged, epilogue=epilogue,
                                  swiglu=swiglu, b_bytes=b_bytes)
            if full > sp.vmem_budget:
                # The planner admits tilings on this full footprint; a plan
                # whose epilogue/swiglu inputs push it past the budget is
                # one the compiler's VMEM limit may refuse.
                v.append(Violation(
                    "vmem_budget_extras",
                    f"working set {full} B incl. epilogue/swiglu inputs "
                    f"exceeds the {sp.vmem_budget} B budget (base {base} B)"))
        v += check_schedule(nsplit=nsplit, fuse=getattr(plan, "fuse", True),
                            epilogue=epilogue, swiglu=swiglu)
        if getattr(plan, "edge", "masked") == "padded":
            v += _pad_priced(family, dims, plan, in_bytes=in_bytes,
                             out_bytes=out_bytes, spec=sp, b_bytes=b_bytes)
    placement = getattr(plan, "placement", None)
    if placement is not None and int(getattr(placement, "num_shards", 1)) > 1:
        v += check_placement(family, dims, placement, spec=sp)
    if (coverage and bm is not None and family in ("dense", "batched")
            and not errors(v)):
        v += verify_contract(variant_contract(family, dims, plan, trans=trans,
                                              swiglu=swiglu))
    return v


def assert_plan(family: str, dims: Sequence[int], plan: Any,
                **kwargs: Any) -> None:
    """Raise ``ContractError`` when any error-severity contract is violated —
    the ``REPRO_VERIFY=1`` dispatch hook."""
    bad = errors(check_plan(family, dims, plan, **kwargs))
    if bad:
        raise ContractError(bad, context=f"{family}{tuple(dims)}")


# ---------------------------------------------------------------------------
# The ragged ordered exception: sorted visit lists
# ---------------------------------------------------------------------------

def check_ragged_visits(offsets: Sequence[int], m_tiles: int, bm: int,
                        gids: Sequence[int], tids: Sequence[int],
                        valid: Sequence[int]) -> list[Violation]:
    """The ragged kernels' masked boundary-tile read-modify-write is sound
    ONLY when the visit list walks tiles in sorted order (the ``first`` flag
    in ``_ragged_store`` keys off the PREVIOUS entry) and groups in sorted
    order (the dW kernel flushes on group change).  Prove it concretely."""
    v: list[Violation] = []
    off = [int(x) for x in offsets]
    if not off or off[0] != 0 or any(b < a for a, b in zip(off, off[1:])):
        return [Violation("bad_offsets",
                          f"group offsets must be a non-decreasing prefix sum "
                          f"starting at 0, got {off[:8]}...")]
    ngroups = len(off) - 1
    vals = [int(x) for x in valid]
    if any(b > a for a, b in zip(vals, vals[1:])):
        v.append(Violation("ragged_valid_not_prefix",
                           "valid flags are not a 1s-prefix; the kernel "
                           "early-outs on the first invalid visit"))
    entries = [(int(g), int(t))
               for g, t, ok in zip(gids, tids, vals) if ok]
    tt = [t for _, t in entries]
    if tt != sorted(tt):
        v.append(Violation(
            "unsorted_visits",
            "visit tile ids are not non-decreasing — the masked boundary-tile "
            "read-modify-write requires same-tile visits adjacent and "
            "ascending (the ordered exception to exactly-once stores)"))
    gg = [g for g, _ in entries]
    if gg != sorted(gg):
        v.append(Violation(
            "unsorted_groups",
            "visit group ids are not non-decreasing — the dW accumulate/flush "
            "keys off group boundaries"))
    if len(set(entries)) != len(entries):
        v.append(Violation("duplicate_visit",
                           "a (group, tile) pair is visited twice — its rows "
                           "would be accumulated twice"))
    expected: set[tuple[int, int]] = set()
    for g in range(ngroups):
        s, e = off[g], off[g + 1]
        for t in range(s // bm, _cdiv(e, bm) if e > s else s // bm):
            expected.add((g, t))
    actual = set(entries)
    missing = expected - actual
    if missing:
        v.append(Violation(
            "ragged_row_uncovered",
            f"{len(missing)} (group, tile) row panels are never visited, "
            f"e.g. {sorted(missing)[:4]} — those output rows are dropped"))
    nonempty_extra = {(g, t) for g, t in actual - expected
                      if off[g + 1] > off[g]}
    if nonempty_extra:
        v.append(Violation(
            "ragged_extra_visit",
            f"visits outside the groups' row ranges: "
            f"{sorted(nonempty_extra)[:4]}"))
    present = set(gg)
    missing_groups = [g for g in range(ngroups)
                      if off[g + 1] == off[g] and g not in present]
    if missing_groups:
        v.append(Violation(
            "ragged_missing_empty_group",
            f"empty groups {missing_groups[:8]} get no forced visit — the dW "
            "kernel would never flush their zero panel", severity="warning"))
    out_of_range = [t for t in tt if not 0 <= t < max(m_tiles, 1)]
    if out_of_range:
        v.append(Violation("out_of_range_store",
                           f"visit tile ids {out_of_range[:4]} outside the "
                           f"{m_tiles} row tiles"))
    return v


def check_ragged_visit_plan(offsets: Sequence[int], bm: int
                            ) -> list[Violation]:
    """Build the ragged visit metadata exactly as the ops wrappers do (via
    ``ops._ragged_metadata`` — concrete evaluation, no kernel launch) and
    check the sorted-visit contract on it."""
    from ..kernels.ftimm import ops as _ops
    import numpy as np
    off = np.asarray(list(offsets), dtype=np.int32)
    total = int(off[-1]) if len(off) else 0
    m_tiles = _ceil_to(max(total, 1), bm) // bm
    gids, tids, valid = _ops._ragged_metadata(off, m_tiles, bm)
    return check_ragged_visits(
        [int(x) for x in off], m_tiles, bm,
        np.asarray(gids).tolist(), np.asarray(tids).tolist(),
        np.asarray(valid).tolist())


# ---------------------------------------------------------------------------
# Cached-record validation (plan_store load-time quarantine)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RecordKey:
    """A parsed ``plan_store.shape_key``."""
    family: str
    dims: tuple[int, ...]
    in_bytes: int
    out_bytes: int
    num_shards: int = 1
    extra: str = ""


def parse_key(key: str) -> RecordKey | None:
    """Parse ``family|MxKxN|ib4|ob4[|extra][|shardsN]`` (the plan_store key
    grammar); ``None`` when malformed."""
    parts = key.split("|")
    if len(parts) < 4:
        return None
    family = parts[0]
    try:
        dims = tuple(int(x) for x in parts[1].split("x"))
        if not (parts[2].startswith("ib") and parts[3].startswith("ob")):
            return None
        in_bytes, out_bytes = int(parts[2][2:]), int(parts[3][2:])
    except ValueError:
        return None
    num_shards, extra = 1, ""
    for p in parts[4:]:
        if p.startswith("shards"):
            try:
                num_shards = int(p[6:])
            except ValueError:
                return None
        else:
            extra = p
    return RecordKey(family, dims, in_bytes, out_bytes, num_shards, extra)


_EXPECTED_NDIMS = {"dense": 3, "batched": 4, "ragged": 4}


def check_record(key: str, rec: Any, spec: Any = None) -> list[Violation]:
    """Validate one cached plan-store record against the static contracts —
    the load-time quarantine gate.  Unknown families pass (forward compat);
    malformed keys/records and contract violations are errors."""
    sp = _spec(spec)
    pk = parse_key(key)
    if pk is None:
        return [Violation("bad_key", f"unparseable plan-store key {key!r}")]
    if pk.family not in FAMILIES:
        return []
    if len(pk.dims) != _EXPECTED_NDIMS[pk.family]:
        return [Violation("bad_key",
                          f"{pk.family} key wants {_EXPECTED_NDIMS[pk.family]}"
                          f" dims, got {pk.dims}")]
    if not isinstance(rec, dict):
        return [Violation("bad_record", "record is not a mapping")]
    try:
        bm, bn, bk = int(rec["bm"]), int(rec["bn"]), int(rec["bk"])
        nsplit = int(rec.get("nsplit", 1))
        dim_order = str(rec.get("dim_order", "mn"))
        edge = str(rec.get("edge", "masked"))
    except (KeyError, TypeError, ValueError):
        return [Violation("bad_record",
                          f"record for {key!r} is missing/mistyping block "
                          "fields")]
    # Parse the extra: "+"-joined variant markers — the ragged axis and the
    # mixed-dtype B width ("bb1" = int8/fp8 weights against wider
    # activations, the dtype axis of the plan key).
    ragged_axis, b_bytes = "m", None
    for part in pk.extra.split("+"):
        if part.startswith("ragged:"):
            ragged_axis = part[len("ragged:"):]
        elif part.startswith("bb"):
            try:
                b_bytes = int(part[2:])
            except ValueError:
                return [Violation("bad_key",
                                  f"unparseable mixed-dtype marker "
                                  f"{part!r} in {key!r}")]
    if b_bytes is not None and nsplit > 1:
        # Conservative quarantine: the measured store never times split-K
        # mixed-dtype variants (the tuner does not generate them), so a
        # cached record claiming one is corrupt or foreign.
        return [Violation(
            "splitk_mixed_dtype",
            f"cached mixed-dtype record (bb{b_bytes}) claims nsplit={nsplit};"
            " no measured split-K mixed-width variant exists")]
    if pk.num_shards > 1:
        strategy = rec.get("strategy")
        if strategy not in STRATEGIES:
            return [Violation("bad_strategy",
                              f"sharded record strategy {strategy!r} not in "
                              f"{STRATEGIES}")]
        schedule = rec.get("schedule", "gather")
        if schedule not in SCHEDULES:
            return [Violation("bad_schedule",
                              f"sharded record schedule {schedule!r} not in "
                              f"{SCHEDULES}")]
        v: list[Violation] = []
        if schedule == "ring" and (pk.family, strategy) not in _RING_LEGAL:
            v.append(Violation(
                "ring_undefined",
                f"ring schedule cached for ({pk.family}, {strategy})"))
        if (strategy == "expert_parallel" and pk.family in ("batched",
                                                            "ragged")
                and pk.dims[0] % pk.num_shards):
            v.append(Violation(
                "ep_indivisible",
                f"{pk.dims[0]} experts cached over {pk.num_shards} shards"))
        if min(bm, bn, bk) <= 0 or nsplit <= 0:
            v.append(Violation("nonpositive_block",
                               f"bm={bm} bn={bn} bk={bk} nsplit={nsplit}"))
        return v
    v = check_blocks(pk.family, pk.dims, bm=bm, bn=bn, bk=bk, nsplit=nsplit,
                     dim_order=dim_order, edge=edge, in_bytes=pk.in_bytes,
                     out_bytes=pk.out_bytes, ragged=ragged_axis, spec=sp)
    if not errors(v):
        footprint = vmem_footprint(pk.family, bm=bm, bn=bn, bk=bk,
                                   in_bytes=pk.in_bytes,
                                   out_bytes=pk.out_bytes, nsplit=nsplit,
                                   ragged=ragged_axis, b_bytes=b_bytes)
        if footprint > sp.vmem_budget:
            v.append(Violation(
                "vmem_budget",
                f"cached record's working set {footprint} B exceeds the "
                f"{sp.vmem_budget} B VMEM budget"))
    return v
