#!/usr/bin/env python3
"""Smoke run of the main path on TPU chips: a start-up check, not a
benchmark.

    python3 chip_smoke.py              # qwen3-1.7b serving on one chip
    python3 chip_smoke.py --chips 4    # expert-parallel MoE + dist_matmul

One chip: ``launch.serve.load_plan_cache``, then ``init_params`` (random
weights from ``--seed``) at the published qwen3-1.7b widths and depth, then
``ServeEngine`` with paged KV serves 8 greedy requests whose prompts fall in
two prefill buckets (8 slots x 64 and x 128 rows).  Every GEMM goes through
the planner to the compiled Pallas ftIMM kernels.  The first bucket's
logits are compared with the same ``prefill_bucket`` on XLA's own dot.

Four chips (``--chips 4``, this phase only): one mixtral-8x7b expert layer
through ``ep_ragged_moe`` under the ring and gather schedules (and gather
again with ``REPRO_RAGGED_A2A=primitive``) against the single-device ragged
layer, and ``dist_matmul`` m_parallel / k_parallel on a T1 and a T2 paper
shape against ``matmul`` on one chip.

Exits non-zero, and prints no result line, when JAX finds no TPU or any
check fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Pallas vs XLA prefill logits.  Errors are max|diff| / max|reference|.
# Both engines take the same bf16 operands, accumulate in fp32 and round
# every GEMM output to bf16, in different accumulation orders; over 28
# layers those roundings compound (a fixed 3e-2 bar, eight ulps of the
# largest logit, was measured exceeded at 3.02e-2 with top-1 agreement 1.0).
# So the bar is the bf16 noise of the run itself: e, XLA's bf16 prefill
# against the same prefill computed in f32, measured in the same run.  Two
# bf16 computations each about e from the f32 result are at most 2e apart
# (triangle inequality); a wrong tile, mask or epilogue is O(1) away.
PREFILL_NOISE_FACTOR = 2.0
# EP MoE layer vs the single-device ragged layer: the same kernels over the
# same groups, with per-shard tilings (a different K blocking changes the
# fp32 accumulation order before the bf16 rounding of the hidden and the
# output).  Two roundings: 4 bf16 ulps of the largest output.
EP_TOL = 1.6e-2
# dist_matmul vs matmul, fp32 outputs from bf16 operands: only the fp32
# summation order of the K-split partials differs.
DIST_TOL = 1e-4

SERVE_ARCH = "qwen3-1.7b"
SERVE_LENGTHS = (40, 48, 56, 64, 72, 90, 110, 128)   # buckets 64 and 128
EP_ARCH = "mixtral-8x7b"
# (schedule, REPRO_RAGGED_A2A) of each EP run; None keeps the probe's pick.
EP_RUNS = (("ring", None), ("gather", None), ("gather", "primitive"))
DIST_SHAPES = ("t1_256k_128", "t2_128_512k")


class Checks:
    """Named pass/fail checks; every one is printed as it is decided."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"check {'ok' if ok else 'FAILED'}: {what}", flush=True)
        if not ok:
            self.failed.append(what)


def compile_counter() -> dict:
    """Running totals of XLA backend-compile seconds and persistent-cache
    hits, fed by JAX's monitoring events."""
    import jax
    totals = {"compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            totals["compile_s"] += duration

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return totals


def rel_err(got, ref) -> float:
    import numpy as np
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def hbm(devices) -> str:
    stats = [d.memory_stats() or {} for d in devices]
    return ", ".join(
        f"{d.id}: in_use {s.get('bytes_in_use', 0) / 2**30:.3f} GiB "
        f"peak {s.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB"
        for d, s in zip(devices, stats))


# ---------------------------------------------------------------------------
# One chip: the serving path
# ---------------------------------------------------------------------------

def xla_prefill(cfg, params, first: dict, slots: int):
    """The recorded first bucket's logits from ``prefill_bucket`` on XLA's
    own dot, every f32 dot at full f32 precision ("highest"; TPU's default
    precision runs an f32 x f32 dot as one bf16 pass)."""
    import jax
    import numpy as np

    from repro.models.model import make_cache, prefill_bucket

    saved = os.environ.get("REPRO_GEMM_BACKEND")
    os.environ["REPRO_GEMM_BACKEND"] = "xla"
    try:
        with jax.default_matmul_precision("highest"):
            ref, _ = jax.jit(functools.partial(prefill_bucket, cfg=cfg))(
                params, batch=first["batch"],
                cache=make_cache(cfg, slots, first["bucket"]),
                lens=first["lens"])
    finally:
        if saved is None:
            os.environ.pop("REPRO_GEMM_BACKEND")
        else:
            os.environ["REPRO_GEMM_BACKEND"] = saved
    return np.asarray(ref)[:, :cfg.vocab_size]


def serve_phase(check: Checks, counter: dict, *, seed: int = 0,
                slots: int = 8, max_new: int = 16,
                lengths=SERVE_LENGTHS, num_layers: int | None = None) -> None:
    """Serve ``lengths``-token greedy prompts through ``ServeEngine`` and
    check the outcome.  ``num_layers`` cuts depth (rehearsals only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.gemm import dispatch, plan_mode_stats
    from repro.launch.serve import load_plan_cache
    from repro.models.model import init_params, make_cache
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config(SERVE_ARCH)
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    print(f"config {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model}"
          f" heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"head_dim={cfg.head_dim_} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} params~{cfg.param_count() / 1e9:.2f}B "
          f"param_dtype={cfg.param_dtype} compute_dtype={cfg.compute_dtype}")
    load_plan_cache(None)
    c0 = dict(counter)
    params = jax.jit(functools.partial(init_params, cfg))(
        jax.random.PRNGKey(seed))
    max_len = max(lengths) + max_new + 8
    engine = ServeEngine(cfg, params, batch_slots=slots, max_len=max_len,
                         seed=seed)
    prefill = engine._bucket_prefill
    prefills: list[dict] = []

    def recording(p, *, batch, cache, lens):
        out = prefill(p, batch=batch, cache=cache, lens=lens)
        rec = {"bucket": int(batch["tokens"].shape[1]),
               "rows": int(batch["tokens"].size)}
        if not prefills:
            rec.update(batch=batch, lens=lens, logits=np.asarray(out[0]))
        prefills.append(rec)
        return out

    engine._bucket_prefill = recording
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(2, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=max_new)
            for i, n in enumerate(lengths)]
    t0 = time.perf_counter()
    engine.run(reqs)
    wall = time.perf_counter() - t0
    n_tok = sum(len(r.out_tokens) for r in reqs)
    print(f"smoke run (not a benchmark): {len(reqs)} requests, {n_tok} "
          f"tokens generated in {wall:.3f} s wall, compile included; "
          f"compile {counter['compile_s'] - c0['compile_s']:.3f} s, "
          f"persistent-cache hits "
          f"{counter['cache_hits'] - c0['cache_hits']}")
    print("prefills (bucket, rows):",
          [(p["bucket"], p["rows"]) for p in prefills])
    modes = plan_mode_stats()
    print("plan modes:", modes)
    health = engine.health()
    print("faults:", health["faults"])
    print("peak HBM:", hbm(jax.devices()[:1]))

    check(all(r.done and not r.shed and not r.timed_out
              and len(r.out_tokens) == max_new
              and all(0 <= t < cfg.vocab_size for t in r.out_tokens)
              for r in reqs),
          f"all {len(reqs)} requests finished with {max_new} in-vocab tokens")
    buckets = {p["bucket"] for p in prefills}
    check(len(buckets) >= 2 and max(p["rows"] for p in prefills) >= 512,
          f"prefill buckets {sorted(buckets)} (>= 2), largest batch "
          f"{max(p['rows'] for p in prefills)} rows (>= 512)")
    check(not modes.get("degraded"), "no degraded servings")
    check(not any(health["faults"].values()), "every fault counter is 0")

    first = prefills[0]
    backend = dispatch._backend()
    n_prefill = prefill.lower(
        params, batch=first["batch"],
        cache=make_cache(cfg, slots, first["bucket"]),
        lens=first["lens"]).as_text().count("tpu_custom_call")
    n_decode = engine._decode.lower(
        params, tokens=jnp.zeros((slots, 1), jnp.int32),
        cache=engine.kv.cache(), pos=jnp.zeros((slots,), jnp.int32),
        page_table=jnp.asarray(engine.kv.table)).as_text().count(
        "tpu_custom_call")
    print(f"gemm backend {backend}: Pallas TPU kernels in the programs: "
          f"prefill {n_prefill}, decode {n_decode}")
    check(backend == "pallas" and n_prefill > 0 and n_decode > 0,
          "GEMMs ran on the compiled Pallas kernels")

    got = first["logits"][:, :cfg.vocab_size]
    xla = xla_prefill(cfg, params, first, slots)
    f32 = xla_prefill(dataclasses.replace(cfg, compute_dtype="float32"),
                      params, first, slots)
    e_px, e_xf, e_pf = rel_err(got, xla), rel_err(xla, f32), rel_err(got, f32)
    print(f"prefill logits (bucket {first['bucket']}, {first['rows']} rows), "
          f"max|diff|/max|ref|: Pallas vs XLA {e_px:.6g}; against the f32 "
          f"prefill: XLA bf16 {e_xf:.6g}, Pallas bf16 {e_pf:.6g}; top-1 "
          f"agreement Pallas/XLA "
          f"{float((got.argmax(-1) == xla.argmax(-1)).mean()):.3f}")
    check(e_px <= PREFILL_NOISE_FACTOR * e_xf,
          f"prefill logits within {PREFILL_NOISE_FACTOR} x XLA's own bf16 "
          f"error ({PREFILL_NOISE_FACTOR * e_xf:.6g}) of XLA's")
    engine.close()


# ---------------------------------------------------------------------------
# Four chips: expert parallelism and the M-/K-parallel dense GEMM
# ---------------------------------------------------------------------------

def _spread(check: Checks, arr, devices, what: str) -> None:
    held = {d.id for d in arr.sharding.device_set}
    check(held == {d.id for d in devices},
          f"{what} spans devices {sorted(held)}")


def mesh_phase(check: Checks, devices, *, seed: int = 0, d: int = 4096,
               f: int = 14336, experts: int = 8, top_k: int = 2,
               tokens: int = 512, dist_shapes=DIST_SHAPES) -> None:
    """Run the cross-chip executors on ``devices`` and compare each with
    its one-chip counterpart."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core import compat
    from repro.core.gemm import (collective, dist_matmul, ep_ragged_moe,
                                 matmul, ragged_matmul, ragged_swiglu)
    from repro.core.gemm.shapes import PAPER_IRREGULAR_SHAPES

    cfg = get_config(EP_ARCH)
    print(f"config {cfg.name}: expert layer d_model={d} d_ff={f} "
          f"experts={experts} top_k={top_k} (published: d_model="
          f"{cfg.d_model} d_ff={cfg.d_ff} experts={cfg.num_experts} "
          f"top_k={cfg.top_k}), {tokens} tokens")
    mesh = compat.make_mesh((len(devices),), ("data",), devices=devices)
    on = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731
    one = jax.sharding.SingleDeviceSharding(devices[0])
    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    def weights(key, shape):
        return jax.jit(lambda k: (jax.random.normal(k, shape, jnp.float32)
                                  / np.sqrt(shape[1])).astype(bf16),
                       out_shardings=on("data"))(key)

    wg = weights(keys[0], (experts, d, f))
    wu = weights(keys[1], (experts, d, f))
    wd = weights(keys[2], (experts, f, d))
    # Top-k routing from random router logits; rows sorted by expert.
    rng = np.random.default_rng(seed)
    choice = np.argsort(-rng.standard_normal((tokens, experts)),
                        axis=1)[:, :top_k].reshape(-1)
    order = np.argsort(choice, kind="stable")
    counts = np.bincount(choice, minlength=experts)
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    x_tok = rng.standard_normal((tokens, d)).astype(np.float32)
    x = jnp.asarray(x_tok[order // top_k], bf16)
    print(f"routed rows per expert: {counts.tolist()}")

    ref = jax.jit(lambda x, wg, wu, wd, o: ragged_matmul(
        ragged_swiglu(x, wg, wu, o), wd, o))(
        jax.device_put(x, one), jax.device_put(wg, one),
        jax.device_put(wu, one), jax.device_put(wd, one),
        jax.device_put(jnp.asarray(offsets), one))
    ref = np.asarray(ref)
    print(f"exchange realization chosen: "
          f"{collective.exchange_method(mesh, ('data',))}")
    x_sh = jax.device_put(x, on("data"))
    offs = jax.device_put(jnp.asarray(offsets), on())
    for schedule, a2a in EP_RUNS:
        saved = os.environ.get(collective.ENV_A2A)
        if a2a is not None:
            os.environ[collective.ENV_A2A] = a2a
        try:
            method = collective.exchange_method(mesh, ("data",))
            out = ep_ragged_moe(x_sh, wg, wu, wd, offs, mesh=mesh,
                                axis="data", schedule=schedule)
            out = jax.block_until_ready(out)
        finally:
            if saved is None:
                os.environ.pop(collective.ENV_A2A, None)
            else:
                os.environ[collective.ENV_A2A] = saved
        err = rel_err(out, ref)
        tag = f"ep_ragged_moe {schedule} (exchange {method})"
        print(f"{tag}: max|diff|/max|ref| = {err:.6g}")
        check(err <= EP_TOL, f"{tag} within {EP_TOL} of one-chip ragged")
        _spread(check, out, devices, f"{tag} output")
    _spread(check, wg, devices, "expert weights")
    print("HBM per device:", hbm(devices))
    per_dev = [(dv.memory_stats() or {}).get("bytes_in_use", 0)
               for dv in devices[1:]]
    shard = 3 * experts * d * f * 2 / len(devices)
    check(min(per_dev) >= shard,
          f"devices {[dv.id for dv in devices[1:]]} each hold >= "
          f"{shard / 2**30:.2f} GiB of expert weights")

    shapes = {name: (m, k, n) for name, m, k, n in PAPER_IRREGULAR_SHAPES}
    for name in dist_shapes:
        m, k, n = shapes[name]
        a = jax.random.normal(keys[3], (m, k), jnp.float32).astype(bf16)
        b = jax.random.normal(jax.random.fold_in(keys[3], 1), (k, n),
                              jnp.float32).astype(bf16)
        want = np.asarray(jax.jit(functools.partial(
            matmul, out_dtype=jnp.float32))(jax.device_put(a, one),
                                            jax.device_put(b, one)))
        for strategy in ("m_parallel", "k_parallel"):
            out = dist_matmul(a, b, mesh=mesh, axis="data",
                              strategy=strategy, out_dtype=jnp.float32)
            out = jax.block_until_ready(out)
            err = rel_err(out, want)
            tag = f"dist_matmul {strategy} {name} ({m}x{k}x{n})"
            print(f"{tag}: max|diff|/max|ref| = {err:.6g}, output sharding "
                  f"{out.sharding}")
            check(err <= DIST_TOL, f"{tag} within {DIST_TOL} of matmul")
            _spread(check, out, devices, f"{tag} output")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serving on one chip; 4: the cross-chip phase "
                         "on jax.devices()[:4]")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this check runs on a TPU only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from importlib.metadata import version

    from repro.runtime.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    counter = compile_counter()
    dev = devices[0]
    print(f"jax {jax.__version__}, jaxlib {version('jaxlib')}, libtpu "
          f"{version('libtpu')}; device {dev.platform} {dev.device_kind} "
          f"x{len(devices)}")
    check = Checks()
    t0 = time.perf_counter()
    if args.chips == 1:
        used = devices[:1]
        serve_phase(check, counter, seed=args.seed)
    else:
        used = devices[:4]
        mesh_phase(check, used, seed=args.seed)
    print(f"phase wall {time.perf_counter() - t0:.3f} s, compile "
          f"{counter['compile_s']:.3f} s, persistent-cache hits "
          f"{counter['cache_hits']}")
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
