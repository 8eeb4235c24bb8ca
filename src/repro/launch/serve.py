"""Serving launcher: batched requests through the slot engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b-smoke \
        --requests 8 --max-new 16 [--plan-cache results/plan_cache.json]

Warmup loads the persistent measured-plan cache (``--plan-cache``, or
``$REPRO_PLAN_CACHE``, or ``results/plan_cache.json`` when present) BEFORE
the engine compiles anything, so every GEMM the serving graphs trace plans
from measured winners (``mode == "cached"``) instead of the raw CMR model.
"""
from __future__ import annotations

import argparse
import os
import pathlib

import jax
import numpy as np

from ..configs import get_config
from ..core.gemm import autotune, epilogue_stats, plan_mode_stats
from ..models.model import init_params
from ..runtime.compile_cache import enable_compile_cache
from ..serve.engine import Request, ServeEngine

_DEFAULT_CACHE = pathlib.Path(__file__).resolve().parents[3] \
    / "results" / "plan_cache.json"


def load_plan_cache(path: str | None) -> int:
    """Serve-warmup plan-cache load: explicit path > env > repo default.
    Returns adopted entries (0 when nothing loadable — serving proceeds on
    analytic plans, it never fails on a missing/corrupt cache)."""
    path = path or os.environ.get(autotune.plan_store.ENV_VAR) \
        or (str(_DEFAULT_CACHE) if _DEFAULT_CACHE.exists() else None)
    if not path:
        return 0
    n = autotune.load_plan_cache(path)
    store = autotune.plan_store.get_store()
    cal = store.calibration
    print(f"plan cache: {n} measured plans from {path}"
          + (f" (calibration flops_frac={cal.flops_frac:.3g} "
               f"bw_frac={cal.bw_frac:.3g})" if cal else ""))
    if store.quarantined:
        # Static verifier rejected these cached records at load; the shapes
        # re-plan analytically instead of silently serving a bad tiling.
        codes = sorted({c for v in store.quarantined.values() for c in v})
        print(f"plan cache: {len(store.quarantined)} records quarantined "
              f"by the static verifier ({', '.join(codes)})")
    return n


def fusion_coverage() -> str:
    """Human-readable epilogue-fusion census of the traced serving graphs:
    how many epilogue-carrying GEMMs ran their elementwise tail fused into
    the kernel/jit vs as separate output passes, per plan family."""
    stats = epilogue_stats()
    if not stats:
        return "(no epilogue-carrying GEMMs traced)"
    fused = sum(v.get("fused", 0) for v in stats.values())
    total = fused + sum(v.get("separate", 0) for v in stats.values())
    per_family = ", ".join(
        f"{fam}: {v.get('fused', 0)}/{v.get('fused', 0) + v.get('separate', 0)}"
        for fam, v in sorted(stats.items()))
    return f"{fused}/{total} fused ({per_family})"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--plan-cache", default=None,
                    help="persistent measured-plan cache to load at warmup")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size (paged families)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV pool size in pages (default: slots x max pages)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline; admission control prices "
                         "against it once calibrated")
    args = ap.parse_args()

    enable_compile_cache()
    load_plan_cache(args.plan_cache)
    cfg = get_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, batch_slots=args.slots,
                         max_len=args.prompt_len + args.max_new + 8,
                         page_size=args.page_size, num_pages=args.num_pages)
    if engine.paged:
        # Constructing the engine priced every bucket through plan_gemm —
        # the plan cache is now warm for exactly the serving signatures.
        cost = engine.cost.snapshot()
        print(f"warmup: buckets={cost['buckets']} "
              f"warmed {cost['warmed_signatures']} GEMM signatures "
              f"(plan-store lookups={cost['store_lookups']} "
              f"hits={cost['store_hits']}), "
              f"KV pool {engine.alloc.total} pages x {engine.page_size} rows")
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(2, cfg.vocab_size,
                                        args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature,
                    deadline_s=args.deadline_s)
            for i in range(args.requests)]
    engine.run(reqs)
    for r in reqs:
        tag = " SHED" if r.shed else (" TIMEOUT" if r.timed_out else "")
        print(f"req {r.rid}{tag}: {r.out_tokens}")
    # plan_mode_stats carries "epilogue"/"degraded" summary entries too; the
    # census and the health snapshot print those dedicated lines instead.
    modes = {fam: v for fam, v in plan_mode_stats().items()
             if fam not in ("epilogue", "degraded")}
    print("plan modes:", modes or "(no planned GEMMs traced)")
    print("epilogue fusion:", fusion_coverage())
    health = engine.health()
    print("health:", "DEGRADED" if health["degraded_mode"] else "ok",
          f"faults={health['faults']}",
          f"degraded_servings={health['degraded_servings'] or '{}'}")
    print("serving done")


if __name__ == "__main__":
    main()
