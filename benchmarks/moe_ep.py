"""MoE dispatch benchmark: capacity vs ragged vs EP-ragged.

Legs of the same (T, D, F, E, top_k) MoE MLP:

  * ``capacity`` — Switch-style static capacity (pad + drop),
  * ``ragged``   — capacity-free sort-by-expert dispatch (PR 2),
  * ``ep_ragged`` — the ragged dispatch expert-sharded over an 8-way axis
    under the planner-chosen schedule (ring overlap since PR 7): measured
    in a SUBPROCESS with 8 fake host devices, because the bench process
    pins its platform device count at jax init,
  * ``ep_ragged_gather`` — the same EP layer with the unoverlapped
    gather-exchange schedule forced (``REPRO_EP_SCHEDULE=gather``), the
    pre-PR-7 behavior kept as the regression reference.

``us_per_call`` is the runnable XLA-CPU wall time (jitted; the 8 fake
devices timeshare one CPU, so EP numbers show schedule overhead, not ICI
speedup — the speedup lives in the modeled column).  The ring schedule
still wins WALL time here because its per-shard compute touches only the
owned token window instead of the worst-case full T.  ``derived`` carries
the planner's view: dispatch rows, the chosen placement strategy+schedule
and the modeled t_total ratio vs the single-device plan at TPU-v5e
constants.

Also writes ``results/BENCH_moe_ep.json`` — the first point of the repo's
perf trajectory; later PRs append comparable runs next to it.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.core.gemm import (plan_moe_dispatch, plan_ragged_gemm,
                             preferred_ep_schedule)
from repro.models.moe import init_moe_params, moe_mlp

from .common import record, time_fn

T, D, F, E, TOP_K = 512, 128, 256, 8, 2
N_SHARDS = 8

_EP_SNIPPET = """
import time
import jax
import jax.numpy as jnp
from repro.core.compat import make_mesh
from repro.core.dist import DistContext, use_dist
from repro.models.moe import init_moe_params, moe_mlp

T, D, F, E, TOP_K = {t}, {d}, {f}, {e}, {top_k}
mesh = make_mesh(({n},), ("data",))
ctx = DistContext(mesh=mesh, dp_axes=("data",), model_axis="data",
                  moe_ep_axis="data")
params = init_moe_params(jax.random.PRNGKey(0), D, F, E)
x = jax.random.normal(jax.random.PRNGKey(1), (T, D))

def step(p, x):
    with use_dist(ctx):
        y, aux = moe_mlp(x, p, num_experts=E, top_k=TOP_K,
                         compute_dtype=jnp.float32, dispatch="ragged")
    return y

f = jax.jit(step)
jax.block_until_ready(f(params, x))
t0 = time.perf_counter()
for _ in range(3):
    jax.block_until_ready(f(params, x))
print("US", (time.perf_counter() - t0) / 3 * 1e6)
"""


def _time_ep_subprocess(schedule: str | None = None) -> float:
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    # A fake-device leg: the child runs on host CPU devices, never on the
    # accelerator this parent process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_SHARDS}"
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    if schedule is not None:
        env["REPRO_EP_SCHEDULE"] = schedule
    else:
        env.pop("REPRO_EP_SCHEDULE", None)
    code = _EP_SNIPPET.format(t=T, d=D, f=F, e=E, top_k=TOP_K, n=N_SHARDS)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return float(out.stdout.strip().split("US")[-1])


def run() -> None:
    params = init_moe_params(jax.random.PRNGKey(0), D, F, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D))

    rows = []

    def leg(name: str, us: float, derived: str):
        record(f"moe_ep_{name}", us, derived)
        rows.append({"name": name, "us_per_call": round(us, 2),
                     "derived": derived})

    for dispatch in ("capacity", "ragged"):
        f = jax.jit(lambda p, x, d=dispatch: moe_mlp(
            x, p, num_experts=E, top_k=TOP_K, compute_dtype=jnp.float32,
            dispatch=d)[0])
        us = time_fn(f, params, x)
        mp = plan_moe_dispatch(T, E, TOP_K, D, F, dispatch=dispatch)
        leg(dispatch, us, f"rows={mp.rows};strategy={mp.strategy}")

    # EP legs: measured in the 8-device subprocess; modeled off the SAME
    # planner the executors consult.  ``ep_ragged`` runs the planner-chosen
    # schedule (ring); ``ep_ragged_gather`` forces the unoverlapped
    # exchange as the pre-ring reference.
    p1 = plan_ragged_gemm(E, T * TOP_K, D, F, 4, 4)
    p8 = plan_ragged_gemm(E, T * TOP_K, D, F, 4, 4, num_shards=N_SHARDS)
    mp8 = plan_moe_dispatch(T, E, TOP_K, D, F, dispatch="ragged",
                            elt_bytes=4, num_shards=N_SHARDS)
    # The schedule the EP executors resolve in the subprocess: the planner
    # preference evaluated with serial=nc (the fake devices timeshare one
    # CPU core, so per-shard local compute serializes).
    schedule = preferred_ep_schedule(E, T * TOP_K, D, F, 4, 4,
                                     num_shards=N_SHARDS, serial=N_SHARDS)
    for name, forced in (("ep_ragged", None), ("ep_ragged_gather", "gather")):
        # A failed child raises: a failed leg is never recorded as a time.
        us_ep = _time_ep_subprocess(forced)
        leg(name, us_ep,
            f"rows={mp8.rows};strategy={p8.placement.strategy};"
            f"schedule={forced or schedule};"
            f"modeled_t1_over_t8={p1.t_total / p8.t_total:.2f};"
            f"a2a_bytes={mp8.placement.ici_bytes:.0f}")

    out = pathlib.Path(__file__).resolve().parents[1] / "results"
    out.mkdir(exist_ok=True)
    payload = {
        "bench": "moe_ep",
        "created": time.strftime("%Y-%m-%d"),
        "config": {"tokens": T, "d_model": D, "d_ff": F, "experts": E,
                   "top_k": TOP_K, "ep_shards": N_SHARDS,
                   "backend": jax.default_backend()},
        "rows": rows,
    }
    with open(out / "BENCH_moe_ep.json", "w") as fp:
        json.dump(payload, fp, indent=1)
