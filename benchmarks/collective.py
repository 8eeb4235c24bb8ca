"""Overlapped-vs-gather collective GEMM benchmark (PR 7).

Runs in a SUBPROCESS with 8 fake host devices (the bench process pins its
platform device count at jax init) and times the PLACED executors
end-to-end — collectives executed, not modeled:

  * dense ``dist_matmul``: m_parallel vs k_parallel/gather (compute then
    psum) vs k_parallel/ring (the overlapped collective matmul),
  * ragged EP ``ep_ragged_matmul``: the single-device reference vs
    expert-parallel under the gather and ring schedules,
  * ``calibrate_ici`` — the fitted effective-ICI-bandwidth fraction (on
    fake host devices this absorbs the software-collective overhead; on a
    real ICI mesh it would sit near 1.0),
  * the crossover-agreement check: does the measured EP winner match the
    schedule ``preferred_ep_schedule`` predicts from the CMR model?  This
    is the gate that the planner's default decision and the hardware agree.

Writes ``results/BENCH_collective.json`` next to the other trajectory
files.  Wall times are XLA-CPU with 8 timesharing fake devices, so sharded
legs cannot beat the single-device leg on wall clock; what the ring legs
demonstrate is per-shard work proportional to OWNED rows instead of the
worst-case full window — which is exactly the term that made the pre-PR-7
EP layer 4.8x slower than one device.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

from .common import record

N_SHARDS = 8
# Ragged shape = the moe_ep bench's dispatch GEMM (T*top_k, D -> F).
G, TOTAL, K, N = 8, 1024, 128, 256
# Dense shape: short M, deep K — the K-parallel regime (paper Alg. 5).
DM, DK, DN = 64, 2048, 256

_SNIPPET = """
import json
import jax
from repro.core.compat import make_mesh
from repro.core.gemm import autotune
from repro.core.gemm.tuner import preferred_ep_schedule

G, TOTAL, K, N = {g}, {total}, {k}, {n}
DM, DK, DN = {dm}, {dk}, {dn}
mesh = make_mesh(({nc},), ("data",))

# Planner predictions FIRST, under the default (uncalibrated) constants
# a fresh process consults.  "predicted" is what the EP executors actually
# resolve here: on the CPU backend the fake devices timeshare one core,
# so the preference is evaluated with the local term serialized over the
# shards (serial=nc) — the same call _resolve_ep_schedule makes.
# "predicted_tpu" is the per-chip (serial=1) preference at TPU constants.
serial = {nc} if jax.default_backend() == "cpu" else 1
predicted = preferred_ep_schedule(G, TOTAL, K, N, 4, 4, num_shards={nc},
                                  serial=serial)
predicted_tpu = preferred_ep_schedule(G, TOTAL, K, N, 4, 4, num_shards={nc})

ragged = autotune.time_placed_ragged_e2e(G, TOTAL, K, N, mesh=mesh,
                                         axis="data", backend="xla")
dense = autotune.time_placed_dense_e2e(DM, DK, DN, mesh=mesh, axis="data",
                                       backend="xla")

# Fit the effective-ICI-bandwidth fraction from timed mesh exchanges and
# report the planner's post-calibration prediction alongside.
cal = autotune.calibrate_ici(mesh, "data")
predicted_cal = preferred_ep_schedule(G, TOTAL, K, N, 4, 4, num_shards={nc},
                                      serial=serial)

print("JSON" + json.dumps({{
    "ragged": ragged, "dense": dense,
    "ici_frac": cal.ici_frac,
    "predicted_schedule": predicted,
    "predicted_schedule_tpu": predicted_tpu,
    "predicted_schedule_calibrated": predicted_cal,
}}))
"""


def _run_subprocess() -> dict:
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    # A fake-device leg: the child runs on host CPU devices, never on the
    # accelerator this parent process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_SHARDS}"
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("REPRO_EP_SCHEDULE", None)
    code = _SNIPPET.format(g=G, total=TOTAL, k=K, n=N, dm=DM, dk=DK, dn=DN,
                           nc=N_SHARDS)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return json.loads(out.stdout.strip().split("JSON")[-1])


def run() -> None:
    rows = []

    def leg(name: str, us: float, derived: str):
        record(f"collective_{name}", us, derived)
        rows.append({"name": name, "us_per_call": round(us, 2),
                     "derived": derived})

    # A failed child raises: a failed leg is never recorded as a result.
    data = _run_subprocess()

    for fam, fam_rows in (("ragged", data["ragged"]),
                          ("dense", data["dense"])):
        for r in fam_rows:
            t_model = r["t_model"]
            model_us = (f"{t_model * 1e6:.1f}"
                        if t_model == t_model else "nan")
            leg(f"{fam}_{r['strategy']}_{r['schedule']}",
                r["t_measured"] * 1e6,
                f"modeled_us={model_us}")

    ep = [r for r in data["ragged"] if r["strategy"] == "expert_parallel"]
    measured_winner = min(ep, key=lambda r: r["t_measured"])["schedule"]
    predicted = data["predicted_schedule"]
    leg("ep_crossover", 0.0,
        f"measured_winner={measured_winner};predicted={predicted};"
        f"agree={measured_winner == predicted};"
        f"predicted_tpu={data['predicted_schedule_tpu']};"
        f"predicted_calibrated={data['predicted_schedule_calibrated']}")
    leg("ici_calibration", 0.0, f"ici_frac={data['ici_frac']:.3e}")

    out = pathlib.Path(__file__).resolve().parents[1] / "results"
    out.mkdir(exist_ok=True)
    payload = {
        "bench": "collective",
        "created": time.strftime("%Y-%m-%d"),
        "config": {"shards": N_SHARDS,
                   "ragged": {"g": G, "total": TOTAL, "k": K, "n": N},
                   "dense": {"m": DM, "k": DK, "n": DN}},
        "rows": rows,
        "ici_frac": data["ici_frac"],
        "predicted_schedule": predicted,
        "predicted_schedule_tpu": data["predicted_schedule_tpu"],
        "measured_winner": measured_winner,
        "crossover_agree": measured_winner == predicted,
        "note": ("8 fake host devices timeshare one CPU: sharded wall "
                 "times bound overhead, not ICI speedup, so the planner "
                 "prediction here is the serial=nc (timeshared-local) "
                 "evaluation the executors use on the CPU backend — the "
                 "ring schedule wins because its per-shard compute covers "
                 "only the owned token window instead of the worst-case "
                 "full T.  predicted_schedule_tpu is the per-chip TPU-v5e "
                 "preference, where this small shape's serialized ring "
                 "rotation bytes favor the gather exchange instead.  "
                 "ici_frac absorbs the software-collective cost and would "
                 "sit near 1.0 on a real ICI mesh; note it is a BANDWIDTH "
                 "fraction fitted on one fused exchange, so it overcharges "
                 "the ring's many small latency-dominated ppermute hops — "
                 "which is why predicted_schedule_calibrated can fall back "
                 "to gather here while measurement (and the uncalibrated "
                 "serialized-local prediction) pick ring."),
    }
    with open(out / "BENCH_collective.json", "w") as fp:
        json.dump(payload, fp, indent=1)
