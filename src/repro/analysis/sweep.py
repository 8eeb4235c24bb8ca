"""Static verification sweep: the repo's zero-device-time correctness ratchet.

    PYTHONPATH=src python -m repro.analysis.sweep \
        [--out results/ANALYSIS_static.json] [--cache results/plan_cache.json]
        [--arch NAME ...] [--quick]

Sweeps the FULL candidate space — every tiling ``tuner.gemm_candidates`` /
``batched_candidates`` / ``ragged_candidates`` would offer the measured
auto-tuner — for the paper's 21 T1/T2/T3 shapes plus GEMM shapes derived
from every registry config (dense projections, MoE ragged/capacity
families), and checks each candidate against the static kernel contracts
(``repro.analysis.contracts``).  Also proves, once per run:

  * the kernel bodies mask the contraction remainder on every operand
    (AST inspection — the 0*NaN hazard);
  * the ragged visit metadata satisfies the sorted-visit contract on a set
    of adversarial group distributions (balanced / skewed / empty groups /
    boundary-sharing), per winning row tile;
  * the symbolic store-coverage proof for each winner's real index maps,
    across all three trans variants;
  * every committed plan-cache record parses and passes ``check_record``
    (what plan-store load would otherwise quarantine at serve time);
  * pruning round-trip: enabling the generators' contract pre-check changes
    no argmin plan (``verify=True`` vs ``verify=False``).

Exit code 1 on any error-severity violation; warnings (e.g. a K-parallel
placement that leaves a shard without a full 128-wide panel) are reported
but never fatal.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Iterable, Sequence

from ..configs.registry import get_config, list_archs
from ..core.gemm import tuner
from ..core.gemm.cmr import TPU_V5E, ceil_to
from ..core.gemm.shapes import PAPER_IRREGULAR_SHAPES
from . import contracts

DECODE_TOKENS = 128     # decode-step rows for registry-derived shapes
# Dtype-axis rows: (in_bytes, out_bytes, b_bytes).  b_bytes=None is the
# homogeneous legacy pair (fp32, bf16); (1, 4, None) is the full-int8
# compute path; b_bytes=1 are the weight-only mixed rows (bf16/fp32
# activations streaming an int8 panel) the quantized dispatch plans with.
_WIDTHS = ((4, 4, None), (2, 2, None), (1, 4, None), (2, 2, 1), (4, 4, 1))
_EPI_OPS = (0, 2)               # identity and bias+activation epilogues


def _dense_jobs(shapes: Sequence[tuple[str, int, int, int]]
                ) -> list[tuple[str, str, tuple[int, ...], str]]:
    return [(name, "dense", (m, k, n), "m") for name, m, k, n in shapes]


def registry_jobs(archs: Iterable[str] | None = None
                  ) -> list[tuple[str, str, tuple[int, ...], str]]:
    """GEMM shapes every registry config actually dispatches at decode:
    dense qkv / attention-out / MLP / LM-head projections, plus the MoE
    ragged (forward and ragged-K dW) and capacity-mode batched families."""
    jobs: list[tuple[str, str, tuple[int, ...], str]] = []
    t = DECODE_TOKENS
    for arch in (archs if archs is not None else list_archs()):
        cfg = get_config(arch)
        d = cfg.d_model
        if cfg.num_heads:
            n_q = cfg.num_heads * cfg.head_dim_
            n_kv = cfg.num_kv_heads * cfg.head_dim_
            jobs.append((f"{arch}:qkv", "dense", (t, d, n_q + 2 * n_kv), "m"))
            jobs.append((f"{arch}:attn_out", "dense", (t, n_q, d), "m"))
        if cfg.d_ff:    # SSM-only archs have no MLP pair to dispatch
            jobs.append((f"{arch}:mlp_up", "dense", (t, d, cfg.d_ff), "m"))
            jobs.append((f"{arch}:mlp_down", "dense", (t, cfg.d_ff, d), "m"))
        jobs.append((f"{arch}:lm_head", "dense", (t, d, cfg.vocab_padded),
                     "m"))
        if cfg.num_experts:
            e, tk = cfg.num_experts, max(cfg.top_k, 1)
            jobs.append((f"{arch}:moe_fwd", "ragged", (e, t * tk, d,
                                                       cfg.d_ff), "m"))
            jobs.append((f"{arch}:moe_dw", "ragged", (e, t * tk, d,
                                                      cfg.d_ff), "k"))
            cap = ceil_to(max(int(t * tk * cfg.capacity_factor) // e, 1), 8)
            jobs.append((f"{arch}:moe_cap", "batched", (e, cap, d, cfg.d_ff),
                         "m"))
    return jobs


def _candidates(family: str, dims: tuple[int, ...], ib: int, ob: int,
                epi_ops: int, ragged: str, verify: bool,
                bb: int | None = None) -> list[Any]:
    if family == "dense":
        m, k, n = dims
        return tuner.gemm_candidates(m, k, n, ib, ob, TPU_V5E, epi_ops,
                                     verify=verify, b_bytes=bb)
    if family == "batched":
        g, m, k, n = dims
        return tuner.batched_candidates(g, m, k, n, ib, ob, "none", TPU_V5E,
                                        epi_ops, verify=verify)
    g, total, k, n = dims
    return tuner.ragged_candidates(g, total, k, n, ib, ob, ragged, TPU_V5E,
                                   verify=verify, b_bytes=bb)


def _argmin(cands: Sequence[Any]) -> Any:
    return min(cands, key=lambda p: p.est.t_total)


# Adversarial group distributions for the ragged sorted-visit proof:
# balanced, heavily skewed, leading/inner empty groups, tile-boundary
# sharing, single group, all-empty-but-one.
_RAGGED_DISTS = (
    lambda g, total: [total * i // g for i in range(g + 1)],
    lambda g, total: [0] + [total] * g,
    lambda g, total: [0, 0] + [total * i // max(g - 1, 1)
                               for i in range(1, g)],
    lambda g, total: [min(7 * i, total) for i in range(g)] + [total],
)


def run_sweep(shapes: Sequence[tuple[str, int, int, int]] | None = None,
              archs: Iterable[str] | None = None,
              cache_path: str | None = "results/plan_cache.json",
              coverage: bool = True) -> dict:
    """Run the full static sweep; returns the findings report (pure data,
    JSON-serializable).  ``report["violations"]`` is the fatal list."""
    shapes = PAPER_IRREGULAR_SHAPES if shapes is None else shapes
    jobs = _dense_jobs(shapes) + registry_jobs(archs)
    violations: list[dict] = []
    warnings: list[dict] = []
    n_checked = 0
    n_jobs = 0
    roundtrip_mismatch: list[str] = []
    coverage_seen: set[tuple] = set()

    def record(name: str, ctx: str, found: Iterable[contracts.Violation]
               ) -> None:
        for v in found:
            row = {"job": name, "context": ctx, "code": v.code,
                   "severity": v.severity, "message": v.message}
            (violations if v.severity == "error" else warnings).append(row)

    for name, family, dims, ragged in jobs:
        n_jobs += 1
        for ib, ob, bb in _WIDTHS:
            if family == "batched" and bb is not None:
                continue    # mixed-width panels: dense/ragged families only
            for epi_ops in (_EPI_OPS if family != "ragged" else (0,)):
                cands = _candidates(family, dims, ib, ob, epi_ops, ragged,
                                    verify=True, bb=bb)
                bbs = "" if bb is None else f" bb{bb}"
                if not cands:
                    record(name, f"ib{ib}{bbs} epi{epi_ops}",
                           [contracts.Violation(
                               "empty_candidates",
                               "generator returned no candidates")])
                    continue
                for plan in cands:
                    n_checked += 1
                    record(name, f"ib{ib}{bbs} epi{epi_ops} bm{plan.bm} "
                                 f"bn{plan.bn} bk{plan.bk} {plan.dim_order} "
                                 f"{plan.edge}",
                           contracts.check_plan(family, dims, plan,
                                                in_bytes=ib, out_bytes=ob,
                                                ragged=ragged, b_bytes=bb))
                # Symbolic store-coverage proof on the winner, all trans
                # variants, deduped by grid geometry across jobs.
                win = _argmin(cands)
                if coverage and family in ("dense", "batched"):
                    for trans in ("nn", "tn", "nt"):
                        c = contracts.variant_contract(family, dims, win,
                                                       trans=trans)
                        sig = (c.name, c.grid, c.out_extent, trans)
                        if sig in coverage_seen:
                            continue
                        coverage_seen.add(sig)
                        record(name, f"coverage {trans}",
                               contracts.verify_contract(c))
                # Pruning round-trip: the contract pre-check must not change
                # the chosen plan (it only removes plans that cannot run).
                unverified = _candidates(family, dims, ib, ob, epi_ops,
                                         ragged, verify=False, bb=bb)
                if unverified and _argmin(unverified) != win:
                    roundtrip_mismatch.append(
                        f"{name} ib{ib}{bbs} epi{epi_ops}")
        if family == "ragged":
            g, total = dims[0], dims[1]
            win = _argmin(_candidates(family, dims, 4, 4, 0, ragged, True))
            tile = win.bm if ragged == "m" else win.bk
            for i, dist in enumerate(_RAGGED_DISTS):
                off = dist(g, total)
                record(name, f"visits dist{i} tile{tile}",
                       contracts.check_ragged_visit_plan(off, tile))

    # Kernel-body mask soundness (once; AST inspection).
    record("kernels", "mask-soundness", contracts.check_contraction_masking())

    if roundtrip_mismatch:
        for ctx in roundtrip_mismatch:
            violations.append({"job": ctx, "context": "prune-roundtrip",
                               "code": "prune_changed_plan",
                               "severity": "error",
                               "message": "contract pre-check changed the "
                                          "argmin plan"})

    # Committed plan-cache records (what load would quarantine).
    cache_report: dict[str, Any] = {"path": cache_path, "entries": 0,
                                    "quarantine_candidates": 0}
    if cache_path:
        try:
            with open(cache_path) as fp:
                blob = json.load(fp)
            entries = blob.get("entries", {}) if isinstance(blob, dict) \
                else {}
        except (OSError, ValueError):
            entries = {}
        cache_report["entries"] = len(entries)
        for key, rec in entries.items():
            found = contracts.errors(contracts.check_record(key, rec))
            if found:
                cache_report["quarantine_candidates"] += 1
                record(key, "plan-cache", found)

    return {
        "jobs": n_jobs,
        "candidates_checked": n_checked,
        "coverage_contracts": len(coverage_seen),
        "plan_cache": cache_report,
        "violations": violations,
        "warnings": warnings,
    }


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="static kernel-contract sweep (no device time)")
    ap.add_argument("--out", default="results/ANALYSIS_static.json",
                    help="findings report path ('' to skip writing)")
    ap.add_argument("--cache", default="results/plan_cache.json",
                    help="committed plan cache to validate ('' to skip)")
    ap.add_argument("--arch", action="append", default=None,
                    help="registry config(s) to sweep (default: all)")
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep (first 6 paper shapes, 2 archs)")
    args = ap.parse_args(argv)

    shapes = PAPER_IRREGULAR_SHAPES
    archs = args.arch
    if args.quick:
        shapes = PAPER_IRREGULAR_SHAPES[:6]
        archs = archs or list_archs()[:2]
    report = run_sweep(shapes=shapes, archs=archs,
                       cache_path=args.cache or None)

    if args.out:
        with open(args.out, "w") as fp:
            json.dump(report, fp, indent=1, sort_keys=True)
    print(f"static sweep: {report['jobs']} shape jobs, "
          f"{report['candidates_checked']} candidate plans, "
          f"{report['coverage_contracts']} store contracts verified, "
          f"{report['plan_cache']['entries']} cached records checked")
    for row in report["warnings"][:10]:
        print(f"  warning {row['code']}: {row['job']} ({row['context']})")
    if len(report["warnings"]) > 10:
        print(f"  ... {len(report['warnings']) - 10} more warnings "
              "(see the JSON report)")
    if report["violations"]:
        for row in report["violations"][:20]:
            print(f"  VIOLATION {row['code']}: {row['job']} "
                  f"({row['context']}): {row['message']}")
        print(f"static sweep: FAIL ({len(report['violations'])} violations)")
        return 1
    print("static sweep: PASS (zero violations)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
