"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh with abstract inputs (ShapeDtypeStruct — zero allocation),
then record memory/cost analysis + collective schedule for the roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k [--multi-pod] [--variant baseline] [--all]

Results land in results/dryrun/<arch>__<shape>__<mesh>__<variant>.json and
are consumed by benchmarks/roofline_table.py and EXPERIMENTS.md.
"""
# The very first statements — before ANY other import, jax locks the device
# count on first init: 512 placeholder CPU devices for the production mesh.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import SHAPES, applicable, get_config, list_archs
from ..core import compat
from ..core.dist import DistContext, use_dist
from ..models.model import make_cache
from ..optim.adamw import OptConfig
from ..roofline.analysis import (build_roofline, collective_bytes,
                                 model_flops_estimate)
from ..roofline.perf_model import step_perf
from ..train.train_step import (make_prefill_step, make_serve_step,
                                make_train_step)
from .mesh import make_production_mesh
from .sharding import (axis_size, batch_specs, cache_specs, dp_axes,
                       expert_axis, param_specs, to_shardings)
from .specs import abstract_state, input_specs

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

# Hillclimb variants: sharding-layout / model knobs applied per run.
#   zero_stage: 3 = params+opt 2-D sharded (baseline); 1 = params TP-only +
#               opt still dp-sharded (ZeRO-1); 0 also for serve layouts.
VARIANTS: dict[str, dict] = {
    "baseline": {},
    "zero1": {"zero_stage": 1},
    "ep_moe": {"moe_ep": True},
    "zero1_ep": {"zero_stage": 1, "moe_ep": True},
    "zero1_ep_buf": {"zero_stage": 1, "moe_ep": True, "moe_buf_shard": True},
    "serve_tp": {"zero_stage": 0},
    "ssm_shard": {"ssm_head_shard": True},
    "zero1_ssm": {"zero_stage": 1, "ssm_head_shard": True},
    "rms_bf16": {"rms_bf16": True},
    "zero1_rms": {"zero_stage": 1, "rms_bf16": True},
    "moe_buf": {"moe_buf_shard": True},
    "sp_v2": {"rms_bf16": True, "sp_inputs": True},
    "sp_v2_zero1": {"rms_bf16": True, "sp_inputs": True, "zero_stage": 1},
    "best_moe": {"rms_bf16": True, "sp_inputs": True, "moe_ep": True,
                 "moe_buf_shard": True},
    "serve_tp_best": {"zero_stage": 0, "rms_bf16": True},
    # mesh re-balance: same 256 chips, trade TP degree for DP (activation
    # collectives scale with per-device batch; grad reduction with 1/TP)
    "mesh32x8": {"mesh": (32, 8)},
    "mesh64x4": {"mesh": (64, 4)},
    "mesh32x8_zero1": {"mesh": (32, 8), "zero_stage": 1},
    "mesh64x4_zero1": {"mesh": (64, 4), "zero_stage": 1},
    "mesh32x8_ep": {"mesh": (32, 8), "moe_ep": True},
    "mesh64x4_dots": {"mesh": (64, 4), "cfg": {"remat": "dots"}},
    "serve_bf16": {"zero_stage": 0, "cfg": {"param_dtype": "bfloat16"}},
    "mesh64x4_ep": {"mesh": (64, 4), "moe_ep": True},
    "l4_ep_model": {"mesh": (32, 8), "moe_ep": True, "moe_ep_axis": "model"},
    "l4_ep_model_bf16p": {"mesh": (32, 8), "moe_ep": True,
                          "moe_ep_axis": "model",
                          "cfg": {"param_dtype": "bfloat16"}},
}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             variant: str = "baseline", save: bool = True,
             opt_overrides: dict | None = None) -> dict:
    knob_cfg = VARIANTS.get(variant, {}).get("cfg")
    if knob_cfg:
        opt_overrides = dict(opt_overrides or {}, **knob_cfg)
    cfg = get_config(arch)
    if opt_overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **opt_overrides)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cellname = f"{arch}__{shape_name}__{mesh_name}__{variant}"
    if not ok:
        result = {"cell": cellname, "status": "skipped", "reason": reason}
        if save:
            _save(cellname, result)
        return result

    knobs = dict(VARIANTS[variant])
    zero_stage = knobs.pop("zero_stage", 3)
    moe_ep = knobs.pop("moe_ep", False)
    moe_ep_axis = knobs.pop("moe_ep_axis", "dp")
    mesh_shape = knobs.pop("mesh", None)
    knobs.pop("cfg", None)
    if mesh_shape is not None:
        from .mesh import make_mesh
        mesh = make_mesh(tuple(mesh_shape), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    dist = DistContext(mesh=mesh, dp_axes=dp_axes(mesh), model_axis="model",
                       moe_ep_axis=expert_axis(mesh, moe_ep, moe_ep_axis,
                                               cfg.num_experts or None),
                       **knobs)
    t0 = time.time()
    with use_dist(dist), mesh:
        batch = input_specs(cfg, shape)
        b_shard = to_shardings(batch_specs(cfg, batch, mesh), mesh)
        if shape.kind == "train":
            params, opt = abstract_state(cfg, shape, with_opt=True)
            p_shard = to_shardings(param_specs(
                params, mesh, zero_stage=zero_stage, moe_ep=moe_ep,
                moe_ep_axis=moe_ep_axis), mesh)
            # ZeRO-1: optimizer state stays dp-sharded even when params are
            # replicated over dp
            o_shard = to_shardings(param_specs(
                opt, mesh, zero_stage=3, moe_ep=moe_ep,
                moe_ep_axis=moe_ep_axis), mesh)
            step = make_train_step(cfg, OptConfig())
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt, batch)
        elif shape.kind == "prefill":
            params, _ = abstract_state(cfg, shape, with_opt=False)
            p_shard = to_shardings(param_specs(
                params, mesh, zero_stage=zero_stage, moe_ep=moe_ep,
                moe_ep_axis=moe_ep_axis), mesh)
            cache = jax.eval_shape(
                lambda: make_cache(cfg, shape.global_batch, shape.seq_len))
            c_shard = to_shardings(cache_specs(cfg, cache, mesh), mesh)
            step = make_prefill_step(cfg)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, b_shard, c_shard),
                             donate_argnums=(2,))
            lowered = jitted.lower(params, batch, cache)
        else:  # decode
            params, _ = abstract_state(cfg, shape, with_opt=False)
            p_shard = to_shardings(param_specs(
                params, mesh, zero_stage=zero_stage, moe_ep=moe_ep,
                moe_ep_axis=moe_ep_axis), mesh)
            cache = jax.eval_shape(
                lambda: make_cache(cfg, shape.global_batch, shape.seq_len))
            c_shard = to_shardings(cache_specs(cfg, cache, mesh), mesh)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            step = make_serve_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, c_shard, b_shard["tokens"], None),
                donate_argnums=(1,))
            lowered = jitted.lower(params, cache, batch["tokens"], pos)

        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compat.cost_analysis(compiled)
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)

    mem_stats = {
        "argument_size": getattr(mem, "argument_size_in_bytes", 0),
        "output_size": getattr(mem, "output_size_in_bytes", 0),
        "temp_size": getattr(mem, "temp_size_in_bytes", 0),
        "peak_memory": (getattr(mem, "argument_size_in_bytes", 0)
                        + getattr(mem, "temp_size_in_bytes", 0)),
    }
    # Price EP off the SAME axis the DistContext routed execution through
    # (axis_size(None) == 1 -> replicated-expert pricing).
    ep_shards = axis_size(mesh, dist.moe_ep_axis) if cfg.num_experts else 1
    perf = step_perf(cfg, shape, ep_shards=ep_shards)
    roof = build_roofline(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        analytic_flops=perf.flops, analytic_bytes=perf.bytes_hbm,
        cost=cost, coll=coll,
        model_flops=model_flops_estimate(cfg, shape, shape.kind),
        memory_stats=mem_stats)
    result = {
        "cell": cellname, "status": "ok", "variant": variant,
        "compile_s": round(t_compile, 1),
        "memory": mem_stats,
        "perf_breakdown": {k: [round(x, 1) for x in v]
                           for k, v in perf.breakdown.items()},
        "roofline": roof.to_dict(),
    }
    if save:
        _save(cellname, result)
    return result


def _save(cellname: str, result: dict) -> None:
    RESULTS.mkdir(parents=True, exist_ok=True)
    with open(RESULTS / f"{cellname}.json", "w") as f:
        json.dump(result, f, indent=1, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell for the given mesh")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    n_ok = n_skip = n_fail = 0
    for arch, shape in cells:
        cellname = f"{arch}__{shape}__{mesh_name}__{args.variant}"
        if args.skip_existing and (RESULTS / f"{cellname}.json").exists():
            prior = json.loads((RESULTS / f"{cellname}.json").read_text())
            if prior.get("status") in ("ok", "skipped"):
                print(f"[skip-existing] {cellname}")
                continue
        try:
            r = run_cell(arch, shape, multi_pod=args.multi_pod,
                         variant=args.variant)
            if r["status"] == "ok":
                n_ok += 1
                roof = r["roofline"]
                print(f"[ok {r['compile_s']}s] {cellname} "
                      f"dominant={roof['dominant']} "
                      f"t_bound={roof['t_bound']:.3e}s "
                      f"mem/dev={r['memory']['peak_memory']/2**30:.2f}GiB")
            else:
                n_skip += 1
                print(f"[skipped] {cellname}: {r['reason']}")
        except Exception as e:  # noqa: BLE001 — record failures per cell
            n_fail += 1
            _save(cellname, {"cell": cellname, "status": "failed",
                             "error": repr(e),
                             "trace": traceback.format_exc()[-4000:]})
            print(f"[FAIL] {cellname}: {e!r}")
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")


if __name__ == "__main__":
    main()
