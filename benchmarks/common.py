"""Shared benchmark utilities: wall-clock timing of the runnable (XLA-CPU)
path + modeled TPU metrics from the CMR/roofline machinery.

This container has no TPU, so each benchmark reports BOTH:
  * ``us_per_call`` — measured wall time of the executable CPU path (jitted
    XLA GEMM / interpret-mode kernel at reduced size where noted), and
  * ``derived``     — the modeled TPU-v5e quantity the paper's figure
    plots (efficiency %, speedup x, GFlops), from the same planner models
    the dry-run validates.
"""
from __future__ import annotations

import csv
import time

import jax
import jax.numpy as jnp

from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache()

ROWS: list[tuple] = []


def record(name: str, us_per_call: float, derived: str) -> None:
    ROWS.append((name, round(us_per_call, 2), derived))
    print(f"{name},{round(us_per_call, 2)},{derived}")


def time_fn(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def rand(shape, dtype=jnp.float32, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def dump_csv(path: str) -> None:
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["name", "us_per_call", "derived"])
        w.writerows(ROWS)
