"""Paper Fig. 7 — efficiency: ftIMM on the accelerator vs a traditional BLAS
on the host CPU (paper: GPDSP cluster vs OpenBLAS on the 16-core ARMv8 of
FT-m7032; ftIMM up to 3.1x higher EFFICIENCY = achieved/peak).

TPU analogue: modeled ftIMM efficiency on v5e vs a fixed-blocking BLAS model
on a host CPU spec (FT-2000+-like: 281.6 GFlops fp32, 42.6 GB/s).  The
figure's quantity is the ratio of efficiencies, which cancels absolute
hardware scale and isolates the blocking/strategy quality — the thing the
paper is actually demonstrating."""
from __future__ import annotations

from repro.core.gemm import plan_gemm, tgemm_plan
from repro.core.gemm.cmr import TPU_V5E, TpuSpec

CPU_SPEC = TpuSpec(name="ft2000plus_cpu", peak_flops_bf16=281.6e9,
                   peak_flops_fp32=281.6e9, hbm_bw=42.6e9,
                   vmem_limit=64 * 1024 * 1024,    # L2-ish blocking budget x2
                   lane=4, sublane_fp32=4, mxu=4)

from .common import record

CASES = [
    ("t1", 2**20, 32, 32),
    ("t2", 32, 2**20, 32),
    ("t3", 20480, 20480, 32),
    ("t3_n96", 20480, 20480, 96),
]


def _efficiency(plan, spec) -> float:
    return plan.est.flops_useful / max(
        plan.est.t_total * spec.peak_flops_fp32, 1e-30)


def run() -> None:
    for name, m, k, n in CASES:
        ours = plan_gemm(m, k, n, spec=TPU_V5E)
        eff_tpu = _efficiency(ours, TPU_V5E)
        # CPU BLAS model: fixed regular blocking on the CPU spec
        cpu_plan = tgemm_plan(m, k, n, spec=CPU_SPEC)
        eff_cpu = _efficiency(cpu_plan, CPU_SPEC)
        record(f"fig7_cpu_compare_{name}", 0.0,
               f"eff_ftimm_tpu={eff_tpu:.3f};eff_blas_cpu={eff_cpu:.3f};"
               f"efficiency_ratio={eff_tpu / max(eff_cpu, 1e-9):.2f}")
