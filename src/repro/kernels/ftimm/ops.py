"""jit'd public wrappers around the ftIMM Pallas kernels.

Handles what the paper calls the "implicit padding" problem.  Two edge
policies exist (``edge=``):

  * ``"masked"`` (default) — zero-copy: unpadded operands go straight to the
    kernels, whose cdiv grids + in-kernel iota masks handle the remainder
    tiles; the output comes back unsliced.  No extra HBM round-trip.
  * ``"padded"`` — the legacy pad -> kernel -> slice path (two extra HBM
    round-trips per GEMM on non-block-multiple shapes).  Kept as the
    comparison point the tuner/benchmarks price and measure against.

The *tuner* (``repro.core.gemm``) chooses blocks that minimize alignment
waste — the very thing the paper's auto-generated micro-kernels achieve over
TGEMM's fixed (m_s=6, n_a=96) kernel — and, since the epilogue generator,
also whether the post-GEMM elementwise tail (bias/activation/residual/scale,
``kernel.Epilogue``) fuses into the accumulator flush.

The kernels are compiled for the TPU.  Interpret mode (the Pallas
interpreter, correct but slow, on any backend) runs only when the caller
passes ``interpret=True``; nothing falls back to it.  The framework's model
code routes through ``repro.core.gemm.dispatch``, which picks the XLA path
on CPU and the Pallas path on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...analysis.contracts import block_aligned, bm_unit
from . import kernel as _k


def _pad_to(x: jax.Array, shape) -> jax.Array:
    pads = [(0, t - s) for s, t in zip(x.shape, shape)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _ceil_to(x: int, b: int) -> int:
    return (x + b - 1) // b * b


def sublane(dtype) -> int:
    """Second-to-minor register tile extent per dtype: (8,128) fp32,
    (16,128) bf16/fp16, (32,128) int8/fp8."""
    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def bench(fn, *args, warmup: int = 1, repeats: int = 3) -> float:
    """Median wall-clock seconds of ``fn(*args)`` — the measured
    auto-tuner's timing primitive.

    Deliberately lives in the ops layer: candidate tilings are timed by
    calling these block-parameterized wrappers DIRECTLY (explicit
    bm/bn/bk), bypassing the planners and their caches entirely, so a
    measurement can never be served by the plan cache it is trying to
    validate.  The first call compiles (jit warms per static-block
    signature); repeats are individually synced with ``block_until_ready``
    and the median taken to shrug off scheduler noise."""
    import time

    jax.block_until_ready(fn(*args))            # compile + first warmup
    for _ in range(max(warmup - 1, 0)):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _clamp_blocks(m: int, k: int, n: int, bm: int, bn: int, bk: int,
                  nsplit: int, dtype,
                  trans: str = "nn") -> tuple[int, int, int, int]:
    """Clamp a plan's blocks to the (rounded) problem extent.

    ``bk`` is clamped exactly like ``bm``/``bn`` — a K=64 problem under a
    bk=512 plan used to pad K 8x (the plan cache can legitimately suggest
    such blocks for a different shape of the same signature family).  A
    clamped ``bk`` may leave ``nsplit`` covering fewer K blocks than splits;
    the split count shrinks with it (degenerating to 1 = the M-parallel
    kernel).  ``bm`` is rounded to its alignment unit under ``trans``
    (``contracts.bm_unit``)."""
    unit = bm_unit(trans, jnp.dtype(dtype).itemsize)
    bm_ = min(_ceil_to(bm, unit), _ceil_to(m, unit))
    bn_ = min(bn, _ceil_to(n, 128))
    bk_ = min(bk, _ceil_to(k, 128))
    if nsplit > 1:
        nsplit = max(1, min(nsplit, -(-_ceil_to(k, 128) // bk_)))
    return bm_, bn_, bk_, nsplit


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm", "bn", "bk", "nsplit", "trans", "dim_order", "out_dtype",
        "interpret", "epilogue", "edge",
    ),
)
def gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    nsplit: int = 1,
    trans: str = "nn",
    dim_order: str = "mn",
    out_dtype=None,
    interpret: bool = False,
    epilogue: "_k.Epilogue | None" = None,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
    edge: str = "masked",
) -> jax.Array:
    """General entry: dispatches to the M-parallel or split-K kernel
    (``nsplit > 1`` selects K-parallel) with the epilogue fused at the flush.
    ``edge="masked"`` passes operands through unpadded (in-kernel edge
    tiles); ``edge="padded"`` pads to block multiples and slices back.
    ``scale`` is the (N,) dequant vector when ``epilogue.scale_vec``."""
    if edge not in ("masked", "padded"):
        raise ValueError(f"unknown edge policy: {edge!r}")
    epilogue = _k.IDENTITY if epilogue is None else epilogue
    out_dtype = out_dtype or a.dtype
    m, k, n = _k._mkn(trans, a.shape, b.shape)
    bm_, bn_, bk_, nsplit = _clamp_blocks(m, k, n, bm, bn, bk, nsplit,
                                          a.dtype, trans)

    if edge == "padded":
        mp, np_ = _ceil_to(m, bm_), _ceil_to(n, bn_)
        kp = _ceil_to(k, bk_ * nsplit) if nsplit > 1 else _ceil_to(k, bk_)
        kp = max(kp, bk_ * nsplit)
        if trans == "nn":
            a_p, b_p = _pad_to(a, (mp, kp)), _pad_to(b, (kp, np_))
        elif trans == "tn":
            a_p, b_p = _pad_to(a, (kp, mp)), _pad_to(b, (kp, np_))
        elif trans == "nt":
            a_p, b_p = _pad_to(a, (mp, kp)), _pad_to(b, (np_, kp))
        else:
            raise ValueError(trans)
        bias_p = None if bias is None else _pad_to(bias, (np_,))
        res_p = None if residual is None else _pad_to(residual, (mp, np_))
        scale_p = None if scale is None else _pad_to(scale, (np_,))
    else:
        if trans not in ("nn", "tn", "nt"):
            raise ValueError(trans)
        a_p, b_p, bias_p, res_p, scale_p = a, b, bias, residual, scale

    if nsplit > 1:
        out = _k.ftimm_gemm_splitk(
            a_p, b_p, bm=bm_, bn=bn_, bk=bk_, nsplit=nsplit, trans=trans,
            out_dtype=out_dtype, interpret=interpret, epilogue=epilogue,
            bias=bias_p, residual=res_p, scale=scale_p,
        )
    else:
        out = _k.ftimm_gemm(
            a_p, b_p, bm=bm_, bn=bn_, bk=bk_, trans=trans,
            dim_order=dim_order, out_dtype=out_dtype, interpret=interpret,
            epilogue=epilogue, bias=bias_p, residual=res_p, scale=scale_p,
        )
    return out if edge == "masked" else out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm", "bn", "bk", "trans", "dim_order", "out_dtype", "interpret",
        "epilogue", "edge",
    ),
)
def batched_gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    trans: str = "nn",
    dim_order: str = "mn",
    out_dtype=None,
    interpret: bool = False,
    epilogue: "_k.Epilogue | None" = None,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
    edge: str = "masked",
) -> jax.Array:
    """Batched/grouped entry.  Either operand may be 2-D (shared across the
    batch — the grouped-GEMM case); the batch dim itself is never padded (it
    maps 1:1 onto the leading grid dim).  ``edge="masked"`` (default) runs
    the kernel on unpadded per-group panels; ``edge="padded"`` is the legacy
    pad/slice path.  ``bias`` and the dequant ``scale`` vector are (N,)
    shared across the batch or (G, N) per group; ``residual`` (G, M, N)."""
    if edge not in ("masked", "padded"):
        raise ValueError(f"unknown edge policy: {edge!r}")
    epilogue = _k.IDENTITY if epilogue is None else epilogue
    out_dtype = out_dtype or a.dtype
    m, k, n = _k._mkn(trans, a.shape[-2:], b.shape[-2:])
    bm_, bn_, bk_, _ = _clamp_blocks(m, k, n, bm, bn, bk, 1, a.dtype,
                                     trans)

    if edge == "padded":
        mp, np_, kp = _ceil_to(m, bm_), _ceil_to(n, bn_), _ceil_to(k, bk_)

        def pad_panels(x, last2):
            return _pad_to(x, x.shape[:-2] + last2)

        def pad_vec(v):
            return None if v is None else _pad_to(v, v.shape[:-1] + (np_,))

        if trans == "nn":
            a_p, b_p = pad_panels(a, (mp, kp)), pad_panels(b, (kp, np_))
        elif trans == "tn":
            a_p, b_p = pad_panels(a, (kp, mp)), pad_panels(b, (kp, np_))
        elif trans == "nt":
            a_p, b_p = pad_panels(a, (mp, kp)), pad_panels(b, (np_, kp))
        else:
            raise ValueError(trans)
        bias_p = pad_vec(bias)
        res_p = None if residual is None else \
            _pad_to(residual, (residual.shape[0], mp, np_))
        scale_p = pad_vec(scale)
    else:
        if trans not in ("nn", "tn", "nt"):
            raise ValueError(trans)
        a_p, b_p, bias_p, res_p, scale_p = a, b, bias, residual, scale

    out = _k.ftimm_gemm_grouped(
        a_p, b_p, bm=bm_, bn=bn_, bk=bk_, trans=trans,
        dim_order=dim_order, out_dtype=out_dtype, interpret=interpret,
        epilogue=epilogue, bias=bias_p, residual=res_p, scale=scale_p,
    )
    return out if edge == "masked" else out[:, :m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret", "edge"),
)
def gemm_swiglu(
    x: jax.Array,                 # (M, K)
    w_gate: jax.Array,            # (K, N)
    w_up: jax.Array,              # (K, N)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
    edge: str = "masked",
) -> jax.Array:
    """Dense fused SwiGLU pair: silu(x @ Wg) * (x @ Wu) in one launch — the
    dense MLP's gate/up projections without the separate silu/mul passes."""
    if edge not in ("masked", "padded"):
        raise ValueError(f"unknown edge policy: {edge!r}")
    out_dtype = out_dtype or x.dtype
    m, k = x.shape
    n = w_gate.shape[1]
    bm_, bn_, bk_, _ = _clamp_blocks(m, k, n, bm, bn, bk, 1, x.dtype)
    if edge == "padded":
        mp, kp, np_ = _ceil_to(m, bm_), _ceil_to(k, bk_), _ceil_to(n, bn_)
        out = _k.ftimm_gemm_swiglu(
            _pad_to(x, (mp, kp)), _pad_to(w_gate, (kp, np_)),
            _pad_to(w_up, (kp, np_)), bm=bm_, bn=bn_, bk=bk_,
            out_dtype=out_dtype, interpret=interpret)
        return out[:m, :n]
    return _k.ftimm_gemm_swiglu(x, w_gate, w_up, bm=bm_, bn=bn_, bk=bk_,
                                out_dtype=out_dtype, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret", "edge"),
)
def batched_gemm_swiglu(
    x: jax.Array,                 # (G, M, K) | (M, K) shared
    w_gate: jax.Array,            # (G, K, N)
    w_up: jax.Array,              # (G, K, N)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
    edge: str = "masked",
) -> jax.Array:
    """Grouped fused SwiGLU pair — the capacity-mode MoE gate/up projections
    (E, C, D) @ (E, D, F) as ONE launch with the silu(gate)*up epilogue."""
    if edge not in ("masked", "padded"):
        raise ValueError(f"unknown edge policy: {edge!r}")
    out_dtype = out_dtype or x.dtype
    m, k = x.shape[-2:]
    g, _, n = w_gate.shape
    bm_, bn_, bk_, _ = _clamp_blocks(m, k, n, bm, bn, bk, 1, x.dtype)
    if edge == "padded":
        mp, kp, np_ = _ceil_to(m, bm_), _ceil_to(k, bk_), _ceil_to(n, bn_)
        x_p = _pad_to(x, x.shape[:-2] + (mp, kp))
        out = _k.ftimm_gemm_grouped_swiglu(
            x_p, _pad_to(w_gate, (g, kp, np_)), _pad_to(w_up, (g, kp, np_)),
            bm=bm_, bn=bn_, bk=bk_, out_dtype=out_dtype, interpret=interpret)
        return out[:, :m, :n]
    return _k.ftimm_gemm_grouped_swiglu(
        x, w_gate, w_up, bm=bm_, bn=bn_, bk=bk_, out_dtype=out_dtype,
        interpret=interpret)


# ---------------------------------------------------------------------------
# Ragged (capacity-free) grouped GEMM
# ---------------------------------------------------------------------------

def _ragged_metadata(group_offsets: jax.Array, m_tiles: int, bm: int):
    """Sorted (row-tile, group) visit list for the ragged kernels.

    ``group_offsets`` is traced (dynamic per-group row counts), so the list is
    built with jnp ops and fed to the kernel as scalar-prefetch operands.  The
    static length is ``m_tiles + G``: every row tile is visited at least once,
    each group boundary inside a tile adds one shared visit, and every *empty*
    group is forced one no-op visit (so the dW kernel flushes a zero panel for
    it).  Entries past the true count carry ``valid == 0`` and repeat the last
    tile / group id — idempotent no-ops for both the masked-store forward and
    the accumulate-then-flush dW walk.
    """
    num_groups = group_offsets.shape[0] - 1
    nt = m_tiles + num_groups
    off = group_offsets.astype(jnp.int32)
    starts = off[:-1] // bm
    ends = (off[1:] + bm - 1) // bm
    sizes = jnp.maximum(ends - starts, 1)        # empty group -> 1 no-op visit
    cum = jnp.cumsum(sizes)
    gids = jnp.repeat(jnp.arange(num_groups, dtype=jnp.int32), sizes,
                      total_repeat_length=nt)
    pos = jnp.arange(nt, dtype=jnp.int32) - (cum - sizes)[gids]
    tids = starts[gids] + pos
    valid = (jnp.arange(nt) < cum[-1]).astype(jnp.int32)
    gids = jnp.where(valid > 0, gids, num_groups - 1).astype(jnp.int32)
    tids = jnp.clip(jnp.where(valid > 0, tids, m_tiles - 1),
                    0, m_tiles - 1).astype(jnp.int32)
    return gids, tids, valid


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "trans", "out_dtype", "interpret",
                     "epilogue"),
)
def ragged_gemm(
    x: jax.Array,                 # (T, K) flat rows, groups contiguous
    w: jax.Array,                 # (G, K, N) "nn" | (G, N, K) "nt"
    group_offsets: jax.Array,     # (G+1,) prefix sums; offsets[G] == T
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    trans: str = "nn",
    out_dtype=None,
    interpret: bool = False,
    epilogue: "_k.Epilogue | None" = None,
    bias: jax.Array | None = None,
    scale: jax.Array | None = None,
) -> jax.Array:
    """Capacity-free grouped GEMM: y[o_g:o_{g+1}] = x[o_g:o_{g+1}] @ W_g.

    Contract: ``group_offsets`` is a non-decreasing int prefix-sum array with
    ``offsets[0] == 0`` and ``offsets[G] == x.shape[0]`` — every row belongs
    to exactly one group (the capacity path's token-dropping has no analogue
    here).  Pads rows/cols to block multiples, builds the visit list, runs the
    scalar-prefetch kernel, un-pads.  ``bias`` / dequant ``scale`` are
    per-expert (G, N) vectors applied at the flush (``epilogue`` flags)."""
    epilogue = _k.IDENTITY if epilogue is None else epilogue
    out_dtype = out_dtype or x.dtype
    t_rows, k = x.shape
    if trans == "nn":
        g, kw, n = w.shape
    elif trans == "nt":
        g, n, kw = w.shape
    else:
        raise ValueError(trans)
    assert kw == k, (x.shape, w.shape, trans)
    assert group_offsets.shape == (g + 1,), (group_offsets.shape, w.shape)
    if t_rows == 0:
        return jnp.zeros((0, n), out_dtype)

    bm_ = min(bm, _ceil_to(t_rows, sublane(x.dtype)))
    bn_ = min(bn, _ceil_to(n, 128))
    bk_ = min(bk, _ceil_to(k, 128))
    # The verifier's alignment check decides the edge path: block-aligned
    # shapes skip the pad AND the output slice entirely (zero-copy).
    if block_aligned((t_rows, k, n), (bm_, bk_, bn_)):
        tp, x_p, w_p, bias_p, scale_p = t_rows, x, w, bias, scale
    else:
        tp, kp, np_ = _ceil_to(t_rows, bm_), _ceil_to(k, bk_), \
            _ceil_to(n, bn_)
        x_p = _pad_to(x, (tp, kp))
        w_p = _pad_to(w, (g, kp, np_) if trans == "nn" else (g, np_, kp))
        bias_p = None if bias is None else _pad_to(bias, (g, np_))
        scale_p = None if scale is None else _pad_to(scale, (g, np_))
    gids, tids, valid = _ragged_metadata(group_offsets, tp // bm_, bm_)
    out = _k.ftimm_gemm_ragged(
        x_p, w_p, gids, tids, valid, group_offsets.astype(jnp.int32),
        bm=bm_, bn=bn_, bk=bk_, trans=trans, out_dtype=out_dtype,
        interpret=interpret, epilogue=epilogue, bias=bias_p, scale=scale_p)
    return out if out.shape == (t_rows, n) else out[:t_rows, :n]


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def ragged_gemm_swiglu(
    x: jax.Array,                 # (T, K)
    w_gate: jax.Array,            # (G, K, N)
    w_up: jax.Array,              # (G, K, N)
    group_offsets: jax.Array,     # (G+1,)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Fused-epilogue ragged pair: silu(x @ Wg_g) * (x @ Wu_g) per group, one
    kernel launch (same contract as ``ragged_gemm``)."""
    out_dtype = out_dtype or x.dtype
    t_rows, k = x.shape
    g, kw, n = w_gate.shape
    assert kw == k and w_up.shape == w_gate.shape, (
        x.shape, w_gate.shape, w_up.shape)
    assert group_offsets.shape == (g + 1,), (group_offsets.shape, w_gate.shape)
    if t_rows == 0:
        return jnp.zeros((0, n), out_dtype)

    bm_ = min(bm, _ceil_to(t_rows, sublane(x.dtype)))
    bn_ = min(bn, _ceil_to(n, 128))
    bk_ = min(bk, _ceil_to(k, 128))
    # Same verifier-driven zero-copy edge path as ragged_gemm.  NOTE: the
    # swiglu kernel has no in-kernel K mask, so the K-aligned requirement
    # from block_aligned is what makes skipping the pad sound.
    if block_aligned((t_rows, k, n), (bm_, bk_, bn_)):
        tp, x_p, wg_p, wu_p = t_rows, x, w_gate, w_up
    else:
        tp, kp, np_ = _ceil_to(t_rows, bm_), _ceil_to(k, bk_), \
            _ceil_to(n, bn_)
        x_p = _pad_to(x, (tp, kp))
        wg_p = _pad_to(w_gate, (g, kp, np_))
        wu_p = _pad_to(w_up, (g, kp, np_))
    gids, tids, valid = _ragged_metadata(group_offsets, tp // bm_, bm_)
    out = _k.ftimm_gemm_ragged_swiglu(
        x_p, wg_p, wu_p, gids, tids, valid, group_offsets.astype(jnp.int32),
        bm=bm_, bn=bn_, bk=bk_, out_dtype=out_dtype, interpret=interpret)
    return out if out.shape == (t_rows, n) else out[:t_rows, :n]


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def ragged_gemm_dw(
    x: jax.Array,                 # (T, D)
    dy: jax.Array,                # (T, F)
    group_offsets: jax.Array,     # (G+1,)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Ragged T2 grouped GEMM: dW[g] = x[rows_g].T @ dy[rows_g] -> (G, D, F).

    ``bk`` tiles the ragged (token) dimension — the contraction; ``bm``/``bn``
    tile the per-group (D, F) output panel.  Same offsets contract as
    ``ragged_gemm``; empty groups yield zero panels."""
    out_dtype = out_dtype or x.dtype
    t_rows, d = x.shape
    t2, f = dy.shape
    g = group_offsets.shape[0] - 1
    assert t2 == t_rows, (x.shape, dy.shape)
    if t_rows == 0:
        return jnp.zeros((g, d, f), out_dtype)

    bk_ = min(bk, _ceil_to(t_rows, sublane(x.dtype)))   # ragged row tiles
    # D is the minor axis of the (bk, bm) x block: bm follows the lane rule.
    bm_ = min(_ceil_to(bm, 128), _ceil_to(d, 128))
    bn_ = min(bn, _ceil_to(f, 128))
    # Verifier-driven zero-copy edge path (ragged axis = contraction here).
    if block_aligned((t_rows, d, f), (bk_, bm_, bn_)):
        tp, x_p, dy_p = t_rows, x, dy
    else:
        tp, dp, fp = _ceil_to(t_rows, bk_), _ceil_to(d, bm_), \
            _ceil_to(f, bn_)
        x_p = _pad_to(x, (tp, dp))
        dy_p = _pad_to(dy, (tp, fp))
    gids, tids, valid = _ragged_metadata(group_offsets, tp // bk_, bk_)
    out = _k.ftimm_gemm_ragged_dw(
        x_p, dy_p, gids, tids, valid, group_offsets.astype(jnp.int32),
        bm=bm_, bn=bn_, bk=bk_, out_dtype=out_dtype, interpret=interpret)
    return out if out.shape == (g, d, f) else out[:, :d, :f]
