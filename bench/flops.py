"""GEMM and roofline arithmetic, from shapes alone, kept with the benchmark
so that no change to the program can move it.  Each model module
(``bench/models/<name>.py``) counts its own layers with these:

* model FLOPs of the real tokens served (``prefill_model_flops``,
  ``decode_model_flops``), which ``serve_mfu`` divides by peak FLOP/s;
* every GEMM one engine call runs, at the shapes it is called with
  (``prefill_gemms``, ``decode_gemms``), as (flops, bytes) from ``gemm``.
  Bytes are each operand read once and the result written once, counted at
  two bytes an element (bfloat16), the least any implementation moves.
  ``gemm_roofline`` divides the least time these take on the chip
  (``roofline_seconds``) by the device time of the GEMM events.
"""
from __future__ import annotations

ELT = 2                 # bytes per element moved: bfloat16
PREFILL_KV_BLOCK = 1024  # the blockwise attention's key block


def gemm(m: float, k: float, n: float, *, b_bytes: float | None = None,
         batch: float = 1) -> tuple[float, float]:
    """(flops, bytes) of ``batch`` products (m, k) x (k, n)."""
    b = batch * k * n * ELT if b_bytes is None else b_bytes
    return 2 * batch * m * k * n, batch * (m * k + m * n) * ELT + b


def roofline_seconds(gemms, peak_flops: float, peak_bw: float) -> float:
    """Least time the chip needs for ``gemms``: per call the larger of its
    FLOPs over peak FLOP/s and its bytes over peak bandwidth."""
    return sum(max(fl / peak_flops, by / peak_bw) for fl, by in gemms)
