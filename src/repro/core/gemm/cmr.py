"""Computation-to-memory-ratio (CMR) model — paper §IV-C Eqs. 1-4, adapted.

The paper derives block sizes by maximizing the CMR of each on-chip memory
level under capacity limits (GSM 6 MB / SM 64 KB / AM 768 KB, DMA'd).  On
TPU the two-level hierarchy is HBM -> VMEM with the Pallas grid pipeline as
the DMA engine, so the adapted model estimates, per candidate tiling:

  * HBM traffic (bytes) given the revisiting/reuse pattern of the grid,
  * padded compute (the cost TGEMM pays for its fixed micro-kernel),
  * a per-shape *upper-bound utilization fraction* — the TPU analogue of the
    paper's broadcast-bandwidth bound (100% for 64 < n_a <= 96, 66.7% for
    n_a <= 32): on TPU the MXU is a 128x128 systolic array, so lanes beyond
    N are dead unless repacked, and streams shorter than ~128 rows pay the
    pipeline-fill latency.

The original paper formulas are kept verbatim (``paper_f1..f4``) so the
benchmarks can reproduce the paper's block-size reasoning next to ours.
"""
from __future__ import annotations

from dataclasses import dataclass


def ceil_to(x: int, b: int) -> int:
    return -(-x // b) * b


def cdiv(x: int, b: int) -> int:
    return -(-x // b)


@dataclass(frozen=True)
class TpuSpec:
    """Per-chip constants of one TPU generation (defaults: v5e)."""
    name: str = "tpu_v5e"
    peak_flops_bf16: float = 197e12
    peak_flops_fp32: float = 98.5e12        # MXU fp32 ~ half bf16 rate
    peak_flops_int8: float = 394e12         # int8 OPS ~ 2x bf16 rate
    hbm_bw: float = 819e9                   # bytes/s
    # Scoped-VMEM limit every ftIMM kernel asks Mosaic for
    # (``vmem_limit_bytes``); v5e has 128 MiB of VMEM, the default scope
    # is 16 MiB.
    vmem_limit: int = 32 * 1024 * 1024
    lane: int = 128                          # vreg lanes / MXU width
    sublane_fp32: int = 8
    sublane_bf16: int = 16
    sublane_int8: int = 32
    mxu: int = 128                           # systolic array edge
    ici_bw_per_link: float = 50e9           # bytes/s per ICI link
    ici_links: int = 4                      # usable links/chip on a 2D torus
    num_chips: int = 1

    @property
    def vmem_budget(self) -> int:
        """What the planner admits a tiling's full per-step footprint
        against (``analysis.contracts.vmem_footprint``): half the compiler
        limit, the rest left to Mosaic's own temporaries."""
        return self.vmem_limit // 2

    def peak_flops(self, dtype_bytes: int) -> float:
        """Peak MXU rate for the *compute* element width.  1-byte operands
        (int8 / fp8) run at the narrow-dtype peak — NOT the bf16 peak the
        pre-quant model fell through to, which overpriced int8 compute 2x."""
        if dtype_bytes >= 4:
            return self.peak_flops_fp32
        if dtype_bytes == 1:
            return self.peak_flops_int8
        return self.peak_flops_bf16

    def sublane(self, dtype_bytes: int) -> int:
        """Register-tile second-to-minor extent: (8,128) fp32, (16,128)
        bf16/fp16, (32,128) int8/fp8 — matches ``kernels.ftimm.sublane``."""
        if dtype_bytes >= 4:
            return self.sublane_fp32
        if dtype_bytes == 1:
            return self.sublane_int8
        return self.sublane_bf16

    def calibrated(self, flops_frac: float, bw_frac: float,
                   ici_frac: float = 1.0,
                   int8_frac: float | None = None) -> "TpuSpec":
        """The measured-effective view of this device: peak FLOP/s scaled by
        the achievable fraction, HBM bandwidth by the effective fraction
        (both fitted by ``autotune.calibrate`` from measured-vs-predicted
        ratios), ICI per-link bandwidth by the effective-ICI fraction
        fitted by ``autotune.calibrate_ici`` from timed mesh exchanges, and
        the int8 peak by its own fitted fraction when the calibration run
        carried narrow-dtype samples (``None`` falls back to the shared
        flops fraction).  Capacities and tile geometry stay nominal — only
        the roofline rates are what measurement corrects."""
        from dataclasses import replace
        return replace(
            self,
            name=f"{self.name}+cal",
            peak_flops_bf16=self.peak_flops_bf16 * flops_frac,
            peak_flops_fp32=self.peak_flops_fp32 * flops_frac,
            peak_flops_int8=self.peak_flops_int8
            * (flops_frac if int8_frac is None else int8_frac),
            hbm_bw=self.hbm_bw * bw_frac,
            ici_bw_per_link=self.ici_bw_per_link * ici_frac,
        )


TPU_V5E = TpuSpec()

# Specs keyed by ``jax.Device.device_kind``.  A v5e chip reports itself as
# "TPU v5 lite".
SPECS_BY_KIND = {"TPU v5 lite": TPU_V5E, "TPU v5e": TPU_V5E}


def spec_for_kind(kind: str) -> TpuSpec:
    """The spec of a TPU ``device_kind``; an unknown kind is an error, not
    a silent v5e default."""
    try:
        return SPECS_BY_KIND[kind]
    except KeyError:
        raise ValueError(
            f"no TpuSpec for TPU device kind {kind!r} (known: "
            f"{', '.join(sorted(SPECS_BY_KIND))})") from None


def device_spec() -> TpuSpec:
    """The spec of this process's first device.  A TPU is looked up by its
    kind; any other backend (the CPU test host) plans against v5e."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return TPU_V5E
    return spec_for_kind(dev.device_kind)


def upper_bound_fraction(m: int, n: int, k: int, spec: TpuSpec = TPU_V5E) -> float:
    """Per-shape upper bound on MXU utilization (paper §IV-A3 analogue).

    Paper: broadcast bandwidth caps small-n_a kernels at 66.7%.  TPU: the
    lane dimension (N) of the MXU below 128 leaves columns dead, a
    contraction below 128 leaves rows dead, and short M streams pay the
    ~MXU-depth pipeline fill.
    """
    lane_frac = min(n, spec.lane) / spec.lane if n < spec.lane else 1.0
    k_frac = min(k, spec.mxu) / spec.mxu if k < spec.mxu else 1.0
    stream_frac = m / (m + spec.mxu)  # pipeline fill amortization
    return lane_frac * k_frac * min(1.0, stream_frac * 2.0)


@dataclass(frozen=True)
class PlanEstimate:
    """Roofline-style estimate for one candidate tiling."""
    flops_useful: float
    flops_padded: float
    hbm_bytes: float
    t_compute: float
    t_memory: float
    vmem_bytes: int
    mxu_fraction: float

    @property
    def t_total(self) -> float:
        # ping-pong / Pallas pipeline: compute overlaps DMA, take the max.
        return max(self.t_compute, self.t_memory)

    @property
    def bound(self) -> str:
        return "compute" if self.t_compute >= self.t_memory else "memory"


def _pad_copy_bytes(orig: int, padded: int, elt_bytes: int) -> float:
    """HBM traffic of one materialized pad (or slice) copy: the original is
    read once and the padded buffer written once (slicing is the mirror
    image).  Zero when already aligned — the copy is elided."""
    if padded == orig:
        return 0.0
    return float(orig + padded) * elt_bytes


def _epilogue_bytes(m: int, n: int, out_bytes: int, epi_ops: int,
                    epi_fused: bool) -> float:
    """Post-GEMM elementwise tail traffic.  Fused into the accumulator flush
    it is free (the output write already happens; bias/residual reads are
    counted small enough to ignore at this altitude); run as ``epi_ops``
    separate XLA passes each one re-reads and re-writes C through HBM."""
    if epi_fused or epi_ops <= 0:
        return 0.0
    return float(epi_ops) * 2.0 * m * n * out_bytes


def estimate(
    m: int, k: int, n: int,
    *,
    bm: int, bn: int, bk: int,
    nsplit: int = 1,
    dim_order: str = "mn",
    in_bytes: int = 4,
    out_bytes: int = 4,
    b_bytes: int | None = None,
    edge: str = "masked",
    epi_ops: int = 0,
    epi_fused: bool = True,
    spec: TpuSpec = TPU_V5E,
) -> PlanEstimate:
    """Model one tiling of C(M,N) += A(M,K) B(K,N) on one TPU core.

    Grid is (outer, inner, K) with K innermost and the fp32 accumulator
    revisited in VMEM (M-parallel), or split-K with ``nsplit`` partials
    reduced through HBM (K-parallel).

    Traffic follows Pallas pipeline semantics: a block is re-fetched whenever
    its index map changes between consecutive grid steps.  When gk == 1 the
    operand indexed only by the *outer* grid dim stays resident across the
    whole inner sweep — the TPU analogue of the paper's "B panel cached in
    GSM" (Alg. 4): e.g. T1 (M >> K ~ N <= 128) with bk=K, bn=ceil(N,128),
    dim_order="nm" streams A exactly once and loads B exactly once.

    ``edge="padded"`` prices the legacy pad -> kernel -> slice wrapper: each
    unaligned operand pays a materialized pad copy and the output a slice
    copy; ``"masked"`` (in-kernel edge tiles) pays nothing extra.  ``epi_ops``
    is the post-GEMM elementwise tail length: fused (``epi_fused``) it rides
    the accumulator flush for free, unfused each op re-reads + re-writes C.

    ``b_bytes`` prices a mixed-width B operand (weight-only quant: bf16
    activations x int8 weights) — B-side traffic, pad copies, and VMEM run
    at the narrow width while the MXU rate is set by the *wider* operand
    (the narrow one upcasts at load).  ``None`` means B matches A.
    """
    bb = in_bytes if b_bytes is None else b_bytes
    mp, np_, kp = ceil_to(m, bm), ceil_to(n, bn), ceil_to(k, bk * nsplit)
    gm, gn, gk = mp // bm, np_ // bn, kp // (bk * nsplit)

    flops_useful = 2.0 * m * n * k
    flops_padded = 2.0 * mp * np_ * kp

    # HBM traffic under index-map-constancy reuse.
    if gk == 1 and nsplit == 1:
        if dim_order == "mn":   # i outer: A resident across the j sweep
            traffic_a = mp * kp * in_bytes
            traffic_b = kp * np_ * gm * bb
        else:                   # j outer: B resident across the i sweep
            traffic_a = mp * kp * gn * in_bytes
            traffic_b = kp * np_ * bb
    else:
        traffic_a = mp * kp * gn * in_bytes
        traffic_b = kp * np_ * gm * bb
    traffic_c = mp * np_ * out_bytes
    if nsplit > 1:
        # Partials written + re-read for the reduction (paper: through GSM;
        # here through HBM within a chip / ICI across chips).
        traffic_c += 2.0 * nsplit * mp * np_ * 4 + mp * np_ * 4
    hbm_bytes = traffic_a + traffic_b + traffic_c
    if edge == "padded":
        # Pad copies in (A, B) and the slice copy out, each a full HBM
        # round-trip the masked path never makes.
        hbm_bytes += _pad_copy_bytes(m * k, mp * kp, in_bytes)
        hbm_bytes += _pad_copy_bytes(k * n, kp * np_, bb)
        hbm_bytes += _pad_copy_bytes(m * n, mp * np_, out_bytes)
    hbm_bytes += _epilogue_bytes(m, n, out_bytes, epi_ops, epi_fused)

    frac = upper_bound_fraction(mp, np_, kp, spec)
    peak = spec.peak_flops(max(in_bytes, bb)) * max(frac, 1e-3)
    t_compute = flops_padded / peak
    t_memory = hbm_bytes / spec.hbm_bw

    # VMEM: double-buffered input blocks + resident fp32 accumulator + out.
    vmem = (2 * (bm * bk * in_bytes + bk * bn * bb)
            + bm * bn * 4
            + 2 * bm * bn * out_bytes)
    return PlanEstimate(
        flops_useful=flops_useful,
        flops_padded=flops_padded,
        hbm_bytes=hbm_bytes,
        t_compute=t_compute,
        t_memory=t_memory,
        vmem_bytes=vmem,
        mxu_fraction=frac,
    )


def estimate_batched(
    g: int, m: int, k: int, n: int,
    *,
    bm: int, bn: int, bk: int,
    dim_order: str = "mn",
    shared_a: bool = False,
    shared_b: bool = False,
    in_bytes: int = 4,
    out_bytes: int = 4,
    b_bytes: int | None = None,
    edge: str = "masked",
    epi_ops: int = 0,
    epi_fused: bool = True,
    spec: TpuSpec = TPU_V5E,
) -> PlanEstimate:
    """Model one tiling of the batched GEMM C(g) += A(g) B(g), g in [0, G).

    Grid is (g, outer, inner, K) with the batch dim outermost.  Per-entry
    traffic follows the same index-map-constancy reuse rule as ``estimate``;
    batched operands then re-fetch for every batch entry (their index map
    carries ``g``), while a *shared* operand (2-D, no batch dim — the grouped
    case) is counted once when the pipeline can actually keep it resident:
    its index map must be globally constant, i.e. a single block in every
    grid dim it reads (gk == 1 and its own outer extent == 1).  Otherwise the
    shared panel re-streams per batch entry exactly like the paper's
    re-fetched operand in the non-cached loop order.  ``b_bytes`` prices a
    mixed-width B operand (see ``estimate``).
    """
    bb = in_bytes if b_bytes is None else b_bytes
    mp, np_, kp = ceil_to(m, bm), ceil_to(n, bn), ceil_to(k, bk)
    gm, gn, gk = mp // bm, np_ // bn, kp // bk

    flops_useful = 2.0 * g * m * n * k
    flops_padded = 2.0 * g * mp * np_ * kp

    # Per-batch-entry traffic under index-map-constancy reuse (cf. estimate).
    if gk == 1:
        if dim_order == "mn":   # i outer: A resident across the j sweep
            ta_entry = mp * kp * in_bytes
            tb_entry = kp * np_ * gm * bb
        else:                   # j outer: B resident across the i sweep
            ta_entry = mp * kp * gn * in_bytes
            tb_entry = kp * np_ * bb
    else:
        ta_entry = mp * kp * gn * in_bytes
        tb_entry = kp * np_ * gm * bb

    a_resident = shared_a and gm == 1 and gk == 1
    b_resident = shared_b and gn == 1 and gk == 1
    traffic_a = (mp * kp * in_bytes) if a_resident else ta_entry * g
    traffic_b = (kp * np_ * bb) if b_resident else tb_entry * g
    traffic_c = g * mp * np_ * out_bytes
    hbm_bytes = traffic_a + traffic_b + traffic_c
    if edge == "padded":
        # Per-group pad copies (a shared 2-D operand pads once) + the
        # per-group output slice copy.
        hbm_bytes += _pad_copy_bytes(m * k, mp * kp, in_bytes) \
            * (1 if shared_a else g)
        hbm_bytes += _pad_copy_bytes(k * n, kp * np_, bb) \
            * (1 if shared_b else g)
        hbm_bytes += _pad_copy_bytes(m * n, mp * np_, out_bytes) * g
    hbm_bytes += _epilogue_bytes(g * m, n, out_bytes, epi_ops, epi_fused)

    frac = upper_bound_fraction(mp, np_, kp, spec)
    peak = spec.peak_flops(max(in_bytes, bb)) * max(frac, 1e-3)
    t_compute = flops_padded / peak
    t_memory = hbm_bytes / spec.hbm_bw

    # VMEM footprint is per grid step — independent of G (batch blocks are 1
    # entry deep), identical to the 2-D kernel's.
    vmem = (2 * (bm * bk * in_bytes + bk * bn * bb)
            + bm * bn * 4
            + 2 * bm * bn * out_bytes)
    return PlanEstimate(
        flops_useful=flops_useful,
        flops_padded=flops_padded,
        hbm_bytes=hbm_bytes,
        t_compute=t_compute,
        t_memory=t_memory,
        vmem_bytes=vmem,
        mxu_fraction=frac,
    )


def estimate_ragged(
    g: int, total: int, k: int, n: int,
    *,
    bm: int, bn: int, bk: int,
    ragged: str = "m",
    in_bytes: int = 4,
    out_bytes: int = 4,
    b_bytes: int | None = None,
    spec: TpuSpec = TPU_V5E,
) -> PlanEstimate:
    """Model one tiling of the ragged grouped GEMM over G groups.

    ``ragged == "m"``: rows of a flat (total, k) operand are chunked per group
    against per-group (k, n) panels — the capacity-free MoE forward.  Priced
    off the *actual* size distribution, i.e. the total row count plus at most
    one shared boundary tile per group — NOT G x max(rows_g) as the static
    capacity path must assume.  ``ragged == "k"``: the ragged dimension is the
    contraction (the backward dW — the paper's T2 regime per group); ``k`` is
    then the per-group output rows (D) and ``n`` the output cols (F).

    Traffic follows the ragged kernels' grids.  Forward (N/bn, NT, K/bk): the
    row operand re-streams once per N-block sweep; when gk == 1 each group's
    panel is fetched once per (j, group) run — the per-group analogue of the
    paper's "B panel cached in GSM"; shared boundary tiles re-store their
    output block (the masked read-modify-write).  dW (D/bm, F/bn, NT): both
    row operands stream once per output-panel block, each group's panel is
    stored once.  ``b_bytes`` prices mixed-width per-group panels (int8
    experts under bf16 tokens — see ``estimate``).
    """
    bb = in_bytes if b_bytes is None else b_bytes
    if ragged == "m":
        tp = ceil_to(max(total, 1), bm)
        visits = tp // bm + max(g - 1, 0)      # boundary tiles, ≤ 1 per group
        np_, kp = ceil_to(n, bn), ceil_to(k, bk)
        gn, gk = np_ // bn, kp // bk
        flops_useful = 2.0 * total * n * k
        flops_padded = 2.0 * visits * bm * np_ * kp
        traffic_x = gn * visits * bm * kp * in_bytes
        if gk == 1:   # panel resident across one group's row tiles
            traffic_w = g * kp * np_ * bb
        else:
            traffic_w = visits * kp * np_ * bb
        # One store per visit per N block; shared-tile visits re-read the
        # block they merge into (read-modify-write).
        traffic_c = visits * bm * np_ * out_bytes \
            + (visits - tp // bm) * bm * np_ * out_bytes
        vmem = (2 * (bm * bk * in_bytes + bk * bn * bb)
                + bm * bn * 4 + 2 * bm * bn * out_bytes)
        frac = upper_bound_fraction(bm, np_, kp, spec)
    elif ragged == "k":
        tp = ceil_to(max(total, 1), bk)
        visits = tp // bk + max(g - 1, 0)
        mp, np_ = ceil_to(k, bm), ceil_to(n, bn)
        gm, gn = mp // bm, np_ // bn
        flops_useful = 2.0 * total * k * n
        flops_padded = 2.0 * visits * bk * mp * np_
        traffic_x = gn * visits * bk * mp * in_bytes
        traffic_w = gm * visits * bk * np_ * bb
        traffic_c = g * mp * np_ * out_bytes
        vmem = (2 * (bk * bm * in_bytes + bk * bn * bb)
                + bm * bn * 4 + 2 * bm * bn * out_bytes)
        frac = upper_bound_fraction(bk, np_, mp, spec)
    else:
        raise ValueError(ragged)

    hbm_bytes = traffic_x + traffic_w + traffic_c
    peak = spec.peak_flops(max(in_bytes, bb)) * max(frac, 1e-3)
    return PlanEstimate(
        flops_useful=flops_useful,
        flops_padded=flops_padded,
        hbm_bytes=hbm_bytes,
        t_compute=flops_padded / peak,
        t_memory=hbm_bytes / spec.hbm_bw,
        vmem_bytes=vmem,
        mxu_fraction=frac,
    )


@dataclass(frozen=True)
class EpEstimate:
    """Modeled cost of ONE expert-parallel all-to-all leg over ICI."""
    ici_bytes: float        # global bytes crossing ICI (all shards summed)
    t_exchange: float       # seconds, set by the BOTTLENECK shard
    imbalance: float = 1.0  # max-shard rows / mean-shard rows

    def __add__(self, other: "EpEstimate") -> "EpEstimate":
        return EpEstimate(self.ici_bytes + other.ici_bytes,
                          self.t_exchange + other.t_exchange,
                          max(self.imbalance, other.imbalance))


EP_ZERO = EpEstimate(0.0, 0.0)


def estimate_ep(
    rows: int, width: int, num_shards: int,
    *,
    elt_bytes: int = 4,
    spec: TpuSpec = TPU_V5E,
    max_shard_rows: int | None = None,
) -> EpEstimate:
    """Price one all-to-all leg of the EP token exchange.

    A (rows, width) token matrix is row-sharded over ``num_shards`` chips,
    and each chip must forward the ``(num_shards - 1) / num_shards``
    fraction of its rows that route to experts owned by other chips.  Each
    chip transmits its share across its ICI links; the exchange is
    bandwidth-bound, so t is a per-chip send time — and like the
    asymmetric-multicore result (slowest participant sets the clock), it is
    the time of the *max* shard, not the mean.  ``max_shard_rows`` is the
    largest per-shard row count when the caller knows the actual group
    distribution; left ``None`` the balanced-routing assumption applies
    (max == mean, imbalance == 1).  One EP GEMM pays TWO legs (dispatch +
    return); callers add the two ``EpEstimate``s.
    """
    if num_shards <= 1:
        return EP_ZERO
    frac = (num_shards - 1) / num_shards
    ici_bytes = float(rows) * width * elt_bytes * frac
    mean_rows = rows / num_shards
    imbalance = 1.0
    if max_shard_rows is not None and mean_rows > 0:
        imbalance = max(1.0, float(max_shard_rows) / mean_rows)
    bottleneck = (ici_bytes / num_shards) * imbalance
    return EpEstimate(ici_bytes,
                      bottleneck / (spec.ici_bw_per_link * spec.ici_links),
                      imbalance)


# ---------------------------------------------------------------------------
# Paper Eqs. 1-4 (verbatim), used by benchmarks/ to reproduce the paper's
# block-size reasoning for FT-m7032 next to the TPU-adapted model above.
# ---------------------------------------------------------------------------

def paper_f1(m_a: float, k_g: float, n_g: float, num_core: int) -> float:
    """Eq. 1 — M-parallel, B panel in GSM; A via SM, C via AM."""
    return (2.0 * m_a * k_g * n_g * num_core) / (
        num_core * m_a * (k_g + 2.0 * n_g) + k_g * n_g)


def paper_f2(m_a: float, k_a: float, n_a: float, num_core: int) -> float:
    """Eq. 2 — M-parallel, B/C blocks resident in AM; A streamed."""
    return (2.0 * m_a * k_a * n_a * num_core) / (
        num_core * m_a * (k_a + 2.0 * n_a) + k_a * n_a)


def paper_f3(m_g: float, k_a: float, n_g: float, num_core: int) -> float:
    """Eq. 3 — K-parallel, C panel in GSM."""
    return (2.0 * m_g * k_a * n_g * num_core) / (
        num_core * k_a * (m_g + n_g) + 2.0 * m_g * n_g)


def paper_f4(m_a: float, k_a: float, n_a: float, num_core: int) -> float:
    """Eq. 4 — K-parallel, AM level."""
    return (2.0 * m_a * k_a * n_a * num_core) / (
        num_core * k_a * (m_a + n_a) + 2.0 * m_a * n_a)
