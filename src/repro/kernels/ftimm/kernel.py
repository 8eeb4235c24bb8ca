"""ftIMM Pallas TPU kernels: shape-specialized tiled GEMM.

Paper mapping (Yin et al., 2022):

* The paper auto-generates assembly micro-kernels per (m_s, k_a, n_a) so that
  small-N GEMMs neither waste AM space nor compute padded lanes.  Here the
  "generator" is a parametric ``pl.pallas_call`` factory: block shapes
  (bm, bn, bk), the grid order, and the split-K factor are free parameters
  chosen by the CMR tuner (``repro.core.gemm``), and Mosaic plays the role of
  the assembler.  The DMA ping-pong double buffering of the paper is the
  Pallas grid pipeline (automatic double-buffering of input blocks between
  sequential grid steps).

* M-parallel strategy (paper Alg. 4)  -> ``ftimm_gemm``: grid over
  (M/bm, N/bn) "parallel" dims with the K loop innermost ("arbitrary"), the
  fp32 accumulator resident in VMEM scratch across K steps (the role GSM/AM
  reuse plays in the paper).

* K-parallel strategy (paper Alg. 5)  -> ``ftimm_gemm_splitk``: the grid
  splits K into ``nsplit`` independent partial products; partials land in an
  fp32 buffer that is reduced afterwards (the paper reduces through GSM; on
  TPU the reduction is an XLA add — and across chips it is a psum over ICI,
  see ``repro.core.gemm.distributed``).

All kernels accumulate in fp32 regardless of input dtype.  Block shapes must
be multiples of the TPU register tiling — (8,128) fp32 / (16,128) bf16 — a
constraint the tuner enforces.  Operand shapes need NOT divide into the
blocks: remainder tiles are handled in-kernel (the grid is ``cdiv``-sized and
the contraction remainder is masked with iota compares), so the ops wrappers
can pass unpadded operands straight through — zero-copy in, unsliced out.
Out-of-range rows/cols of edge blocks read as garbage (Mosaic) / NaN
(interpret) but only ever land in output elements the store drops; only the
contraction dimension's garbage could poison valid outputs, hence only it is
masked (both operands — 0 * NaN is NaN, so masking one side is not enough).

Epilogues: every kernel family takes an ``Epilogue`` spec applied to the fp32
accumulator at the flush (scale -> bias add -> activation -> residual add ->
output cast), so dense model layers stop running silu/bias/residual as
separate XLA passes over the output.  The fused ``silu(x@Wg) * (x@Wu)`` pair
exists as a dense (``ftimm_gemm_swiglu``) and grouped
(``ftimm_gemm_grouped_swiglu``) two-accumulator variant mirroring the ragged
one.  The split-K kernel applies the epilogue after its partials reduction
(the activation is nonlinear; flushing it per split would be wrong).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.compat import pallas_compiler_params, prefetch_scalar_grid_spec
from .epilogue import IDENTITY, Epilogue

DimOrder = Literal["mn", "nm"]


def _compiler_params(*dimension_semantics: str):
    """Mosaic params of every ftIMM kernel: the grid semantics plus an
    explicit scoped-VMEM limit, the ``TpuSpec.vmem_limit`` of this device
    — the same field the planner's VMEM budget derives from, so the
    planner and the compiler agree on one number."""
    from ...core.gemm.cmr import device_spec
    return pallas_compiler_params(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=device_spec().vmem_limit)


def _k_limit(k_total: int, bk: int, kb_idx):
    """Valid contraction extent of K block ``kb_idx`` — ``bk`` for interior
    blocks, the remainder for the edge block, 0 for fully out-of-range blocks
    (split-K grids can produce those)."""
    return jnp.clip(k_total - kb_idx * bk, 0, bk)


def _mask_contract(blk, k_lim, dim: int):
    """Zero a block's out-of-range contraction rows/cols (iota compare)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, blk.shape, dim)
    return jnp.where(iota < k_lim, blk, jnp.zeros_like(blk))


def _unpack_epi(rest, epi: Epilogue):
    """Split a kernel's trailing refs into (bias, residual, scale, c,
    *scratch) — same bias -> residual -> scale order as ``Epilogue.unpack``.
    """
    i = 0
    bias_ref = rest[i] if epi.bias else None
    i += int(epi.bias)
    res_ref = rest[i] if epi.residual else None
    i += int(epi.residual)
    scale_ref = rest[i] if epi.scale_vec else None
    i += int(epi.scale_vec)
    return bias_ref, res_ref, scale_ref, rest[i], rest[i + 1:]


def _acc_dtype(a_dtype, b_dtype):
    """Accumulator dtype under the dtype axis: int x int accumulates in
    int32 (the int8 MXU contract); every float combination (incl. fp8 and
    the mixed weight-only case) accumulates in fp32."""
    if (jnp.issubdtype(jnp.dtype(a_dtype), jnp.integer)
            and jnp.issubdtype(jnp.dtype(b_dtype), jnp.integer)):
        return jnp.int32
    return jnp.float32


def _dot_operands(a_blk, b_blk):
    """Prepare the operand pair for the MXU dot under the dtype axis.

    int x int passes through (int32 accumulate).  Mixed float x int — the
    weight-only-quant path — upcasts the integer operand to the float
    operand's dtype AT LOAD (the in-kernel dequant step; the scale applies
    at the flush).  fp8 operands upcast to fp32 before the dot so the same
    kernel body runs under interpret mode / CPU lowering."""
    a_int = jnp.issubdtype(a_blk.dtype, jnp.integer)
    b_int = jnp.issubdtype(b_blk.dtype, jnp.integer)
    if a_int and b_int:
        return a_blk, b_blk
    if a_int:
        return a_blk.astype(b_blk.dtype), b_blk
    if b_int:
        return a_blk, b_blk.astype(a_blk.dtype)
    if a_blk.dtype.itemsize == 1 or b_blk.dtype.itemsize == 1:
        return a_blk.astype(jnp.float32), b_blk.astype(jnp.float32)
    return a_blk, b_blk


def _accum_body(a_blk, b_blk, c_ref, acc_ref, *, k, nk, dims, k_lim=None,
                epi: Epilogue = IDENTITY, bias_ref=None, res_ref=None,
                scale_ref=None):
    """Shared accumulate-and-flush body across all kernel variants."""

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if k_lim is not None:
        a_blk = _mask_contract(a_blk, k_lim, dims[0][0])
        b_blk = _mask_contract(b_blk, k_lim, dims[1][0])
    a_blk, b_blk = _dot_operands(a_blk, b_blk)
    acc_ref[...] += jax.lax.dot_general(
        a_blk, b_blk, (dims, ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(k == nk - 1)
    def _flush():
        acc = acc_ref[...]
        if not epi.is_identity:
            acc = epi.apply(
                acc.astype(jnp.float32),
                bias=None if bias_ref is None else bias_ref[...],
                residual=None if res_ref is None else res_ref[...],
                scale=None if scale_ref is None else scale_ref[...])
        c_ref[...] = acc.astype(c_ref.dtype)


def _dense_kernel(a_ref, b_ref, *rest, nk, dims, bk, k_total, mask_k,
                  epi: Epilogue):
    bias_ref, res_ref, scale_ref, c_ref, (acc_ref,) = _unpack_epi(rest, epi)
    k = pl.program_id(2)
    k_lim = _k_limit(k_total, bk, k) if mask_k else None
    _accum_body(a_ref[...], b_ref[...], c_ref, acc_ref, k=k, nk=nk,
                dims=dims, k_lim=k_lim, epi=epi, bias_ref=bias_ref,
                res_ref=res_ref, scale_ref=scale_ref)


def _specs(trans: str, bm: int, bn: int, bk: int, order: DimOrder):
    """BlockSpecs for each operand layout under a given grid order.

    Grid is (outer, inner, k) with k innermost so the fp32 accumulator block
    is revisited across K steps (paper: C_a stays in AM during the k_g loop).
    ``order`` decides whether the M or the N dimension is the outer parallel
    loop — the paper's loop-order-for-reuse discussion: the operand indexed
    by the *inner* dim is re-fetched per outer step, the other is reused.
    """
    if order == "mn":
        i_of = lambda i, j, k: i   # noqa: E731
        j_of = lambda i, j, k: j   # noqa: E731
    else:
        i_of = lambda i, j, k: j   # noqa: E731
        j_of = lambda i, j, k: i   # noqa: E731
    if trans == "nn":
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i_of(i, j, k), k))
        b_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j_of(i, j, k)))
    elif trans == "tn":
        a_spec = pl.BlockSpec((bk, bm), lambda i, j, k: (k, i_of(i, j, k)))
        b_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j_of(i, j, k)))
    elif trans == "nt":
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i_of(i, j, k), k))
        b_spec = pl.BlockSpec((bn, bk), lambda i, j, k: (j_of(i, j, k), k))
    else:  # pragma: no cover
        raise ValueError(trans)
    c_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i_of(i, j, k), j_of(i, j, k)))
    bias_spec = pl.BlockSpec((1, bn), lambda i, j, k: (0, j_of(i, j, k)))
    return a_spec, b_spec, c_spec, bias_spec


def _mkn(trans: str, a_shape, b_shape):
    if trans == "nn":
        (m, k), (_, n) = a_shape, b_shape
    elif trans == "tn":
        (k, m), (_, n) = a_shape, b_shape
    else:  # nt
        (m, k), (n, _) = a_shape, b_shape
    return m, k, n


_DIMS = {"nn": ((1,), (0,)), "tn": ((0,), (0,)), "nt": ((1,), (1,))}


def ftimm_gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int,
    bn: int,
    bk: int,
    trans: str = "nn",
    dim_order: DimOrder = "mn",
    out_dtype=None,
    interpret: bool = False,
    epilogue: Epilogue = IDENTITY,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
) -> jax.Array:
    """M-parallel ftIMM GEMM.  Shapes need not be block multiples: the grid
    is cdiv-sized and remainder K tiles are masked in-kernel (zero-copy edge
    tiles); out-of-range output elements are dropped by the store.

    trans: "nn" A(M,K)@B(K,N); "tn" A(K,M).T@B(K,N); "nt" A(M,K)@B(N,K).T.
    ``epilogue`` is applied to the fp32 accumulator at the flush; ``bias``
    (N,), ``residual`` (M, N) and the dequant ``scale`` vector (N,) ride
    along as extra inputs when the spec asks for them.  Integer x integer
    operands accumulate in int32 (the int8 path); mixed float x int
    operands dequantize at load (weight-only quant).
    """
    m, k, n = _mkn(trans, a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    gm, gn, gk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk)
    grid = (gm, gn, gk) if dim_order == "mn" else (gn, gm, gk)
    a_spec, b_spec, c_spec, bias_spec = _specs(trans, bm, bn, bk, dim_order)
    in_specs, inputs = [a_spec, b_spec], [a, b]
    if epilogue.bias:
        in_specs.append(bias_spec)
        inputs.append(bias.reshape(1, n))
    if epilogue.residual:
        in_specs.append(c_spec)
        inputs.append(residual)
    if epilogue.scale_vec:
        in_specs.append(bias_spec)
        inputs.append(scale.reshape(1, n).astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_dense_kernel, nk=gk, dims=_DIMS[trans], bk=bk,
                          k_total=k, mask_k=bool(k % bk), epi=epilogue),
        grid=grid,
        in_specs=in_specs,
        out_specs=c_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), _acc_dtype(a.dtype, b.dtype))],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*inputs)


def _batched_kernel(a_ref, b_ref, *rest, nk, dims, a_batched, b_batched,
                    bk, k_total, mask_k, epi: Epilogue):
    bias_ref, res_ref, scale_ref, c_ref, (acc_ref,) = _unpack_epi(rest, epi)
    a_blk = a_ref[0] if a_batched else a_ref[...]
    b_blk = b_ref[0] if b_batched else b_ref[...]
    k = pl.program_id(3)
    k_lim = _k_limit(k_total, bk, k) if mask_k else None
    _accum_body(a_blk, b_blk, c_ref.at[0], acc_ref, k=k, nk=nk, dims=dims,
                k_lim=k_lim, epi=epi, bias_ref=bias_ref,
                res_ref=None if res_ref is None else res_ref.at[0],
                scale_ref=scale_ref)


def _batched_specs(trans: str, bm: int, bn: int, bk: int, order: DimOrder,
                   a_batched: bool, b_batched: bool):
    """BlockSpecs for the (g, outer, inner, k) grid.

    Batched operands carry a leading size-1 block indexed by the batch grid
    dim; a *shared* (2-D) operand's index map simply omits ``g`` — the Pallas
    pipeline then keeps its block resident across consecutive batch entries
    whenever the rest of the index map is constant (the grouped-GEMM analogue
    of the paper's "B panel cached in GSM" reuse, now across the batch)."""
    if order == "mn":
        i_of = lambda g, i, j, k: i   # noqa: E731
        j_of = lambda g, i, j, k: j   # noqa: E731
    else:
        i_of = lambda g, i, j, k: j   # noqa: E731
        j_of = lambda g, i, j, k: i   # noqa: E731

    def spec(batched: bool, shape2, idx2):
        if batched:
            return pl.BlockSpec(
                (1,) + shape2, lambda g, i, j, k: (g,) + idx2(g, i, j, k))
        return pl.BlockSpec(shape2, lambda g, i, j, k: idx2(g, i, j, k))

    if trans == "nn":
        a_spec = spec(a_batched, (bm, bk),
                      lambda g, i, j, k: (i_of(g, i, j, k), k))
        b_spec = spec(b_batched, (bk, bn),
                      lambda g, i, j, k: (k, j_of(g, i, j, k)))
    elif trans == "tn":
        a_spec = spec(a_batched, (bk, bm),
                      lambda g, i, j, k: (k, i_of(g, i, j, k)))
        b_spec = spec(b_batched, (bk, bn),
                      lambda g, i, j, k: (k, j_of(g, i, j, k)))
    elif trans == "nt":
        a_spec = spec(a_batched, (bm, bk),
                      lambda g, i, j, k: (i_of(g, i, j, k), k))
        b_spec = spec(b_batched, (bn, bk),
                      lambda g, i, j, k: (j_of(g, i, j, k), k))
    else:  # pragma: no cover
        raise ValueError(trans)
    c_spec = pl.BlockSpec(
        (1, bm, bn),
        lambda g, i, j, k: (g, i_of(g, i, j, k), j_of(g, i, j, k)))
    bias_spec = pl.BlockSpec((1, bn), lambda g, i, j, k: (0, j_of(g, i, j, k)))
    # Per-group variant: the (N,)-wide vector is indexed by the batch grid
    # dim — one bias/scale row per group (the per-expert epilogue).
    gbias_spec = pl.BlockSpec(
        (1, bn), lambda g, i, j, k: (g, j_of(g, i, j, k)))
    return a_spec, b_spec, c_spec, bias_spec, gbias_spec


def ftimm_gemm_grouped(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int,
    bn: int,
    bk: int,
    trans: str = "nn",
    dim_order: DimOrder = "mn",
    out_dtype=None,
    interpret: bool = False,
    epilogue: Epilogue = IDENTITY,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
) -> jax.Array:
    """Grouped ftIMM GEMM: per-group operands with optional sharing.

    Either operand may be 3-D ``(G, ., .)`` (one panel per group — the MoE
    expert-weight case ``(E, C, D) @ (E, D, F)``) or 2-D (one panel shared by
    every group, e.g. a common activation against per-group weights or vice
    versa).  At least one operand must be 3-D.  Per-group shapes need not be
    block multiples (remainder K tiles masked in-kernel); returns
    ``(G, M, N)``.  ``epilogue`` flushes fused: ``bias`` is (N,) shared
    across the batch or (G, N) per group (the per-expert epilogue), and the
    same for the dequant ``scale`` vector; ``residual`` is (G, M, N).
    """
    a_batched, b_batched = a.ndim == 3, b.ndim == 3
    assert a_batched or b_batched, (a.shape, b.shape)
    if a_batched and b_batched:
        assert a.shape[0] == b.shape[0], (a.shape, b.shape)
    gsize = a.shape[0] if a_batched else b.shape[0]
    m, k, n = _mkn(trans, a.shape[-2:], b.shape[-2:])
    out_dtype = out_dtype or a.dtype
    gm, gn, gk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk)
    grid = ((gsize, gm, gn, gk) if dim_order == "mn"
            else (gsize, gn, gm, gk))
    a_spec, b_spec, c_spec, bias_spec, gbias_spec = _batched_specs(
        trans, bm, bn, bk, dim_order, a_batched, b_batched)

    def vec_arg(v):
        """(N,) shared vs (G, N) per-group (N,)-wide epilogue operand."""
        if v.ndim == 2:
            assert v.shape == (gsize, n), (v.shape, gsize, n)
            return gbias_spec, v
        return bias_spec, v.reshape(1, n)

    in_specs, inputs = [a_spec, b_spec], [a, b]
    if epilogue.bias:
        spec, arg = vec_arg(bias)
        in_specs.append(spec)
        inputs.append(arg)
    if epilogue.residual:
        in_specs.append(c_spec)
        inputs.append(residual)
    if epilogue.scale_vec:
        spec, arg = vec_arg(scale.astype(jnp.float32))
        in_specs.append(spec)
        inputs.append(arg)
    return pl.pallas_call(
        functools.partial(_batched_kernel, nk=gk, dims=_DIMS[trans],
                          a_batched=a_batched, b_batched=b_batched, bk=bk,
                          k_total=k, mask_k=bool(k % bk), epi=epilogue),
        grid=grid,
        in_specs=in_specs,
        out_specs=c_spec,
        out_shape=jax.ShapeDtypeStruct((gsize, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), _acc_dtype(a.dtype, b.dtype))],
        compiler_params=_compiler_params(
            "parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*inputs)


def ftimm_gemm_batched(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int,
    bn: int,
    bk: int,
    trans: str = "nn",
    dim_order: DimOrder = "mn",
    out_dtype=None,
    interpret: bool = False,
    epilogue: Epilogue = IDENTITY,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
) -> jax.Array:
    """Batched ftIMM GEMM: leading batch grid dim over independent per-entry
    GEMMs, ``(G, M, K) @ (G, K, N) -> (G, M, N)`` (trans variants as in
    ``ftimm_gemm``).  The accumulator is revisited across the innermost
    K steps exactly as in the 2-D kernel; each batch entry owns its own
    output block so the batch dim is fully parallel."""
    assert a.ndim == 3 and b.ndim == 3, (a.shape, b.shape)
    return ftimm_gemm_grouped(
        a, b, bm=bm, bn=bn, bk=bk, trans=trans, dim_order=dim_order,
        out_dtype=out_dtype, interpret=interpret, epilogue=epilogue,
        bias=bias, residual=residual, scale=scale)


# ---------------------------------------------------------------------------
# Ragged (capacity-free) grouped GEMM — megablocks-style.
#
# Rows of a flat (T, K) operand are partitioned into G contiguous groups by a
# ``group_offsets`` prefix-sum array (dynamic values — the per-expert token
# counts of a capacity-free MoE dispatch).  The kernel walks a sorted list of
# (row-tile, group) visits; the visit list is *data-dependent*, so its
# ``group_ids`` / ``tile_ids`` arrays arrive via scalar prefetch and drive the
# BlockSpec index maps (which expert's weight panel to DMA for each step) —
# the ragged analogue of the paper's per-shape micro-kernel selection, decided
# per row-tile instead of per call.
#
# A row tile shared by several groups is visited once per group; each visit
# computes the full tile product against its own group's panel and stores only
# its own rows (masked read-modify-write).  Visits of the same tile are
# adjacent in the sorted list, so the output block stays VMEM-resident between
# them and the first visit zero-fills rows owned by no group (row padding).
# The static visit-list length is T/bm + G (every boundary adds at most one
# shared tile; empty groups get one forced no-op visit so each group id
# appears — see ops._ragged_metadata); padded tail entries have ``valid == 0``
# and mask to no-ops.
# ---------------------------------------------------------------------------


def _ragged_row_mask(offs_ref, g, tile, valid, shape, bm):
    """Rows of this (bm, .) tile owned by group ``g`` — empty when invalid."""
    rows = tile * bm + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= offs_ref[g]) & (rows < offs_ref[g + 1]) & (valid > 0)


def _ragged_store(gids_ref, tids_ref, valid_ref, offs_ref, o_ref, acc,
                  *, t, bm):
    """Masked read-modify-write of one output row tile.

    First visit of a tile zero-fills the rows outside the mask; later visits
    (same tile, next group — adjacent grid steps, block resident) preserve
    them.  Reading ``o_ref`` on a first visit would be garbage, but the
    ``where`` never selects it then."""
    g, tile = gids_ref[t], tids_ref[t]
    mask = _ragged_row_mask(offs_ref, g, tile, valid_ref[t], acc.shape, bm)
    first = (t == 0) | (tile != tids_ref[jnp.maximum(t - 1, 0)])
    prev = jnp.where(first, 0.0, o_ref[...].astype(jnp.float32))
    o_ref[...] = jnp.where(mask, acc, prev).astype(o_ref.dtype)


def _ragged_kernel(gids_ref, tids_ref, valid_ref, offs_ref,
                   x_ref, w_ref, *rest, nk, bm, dims, epi: Epilogue):
    i = 0
    bias_ref = rest[i] if epi.bias else None
    i += int(epi.bias)
    scale_ref = rest[i] if epi.scale_vec else None
    i += int(epi.scale_vec)
    o_ref, acc_ref = rest[i], rest[i + 1]
    t, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x_blk, w_blk = _dot_operands(x_ref[...], w_ref[0])
    acc_ref[...] += jax.lax.dot_general(
        x_blk, w_blk, (dims, ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(k == nk - 1)
    def _flush():
        # The per-expert bias/scale blocks arrive pre-indexed by this
        # visit's group id; applying them to the WHOLE tile accumulator is
        # sound because the masked store below only lands this group's rows
        # — foreign rows (computed against the wrong panel anyway) drop.
        acc = acc_ref[...]
        if not epi.is_identity:
            acc = epi.apply(
                acc.astype(jnp.float32),
                bias=None if bias_ref is None else bias_ref[0],
                scale=None if scale_ref is None else scale_ref[0])
        _ragged_store(gids_ref, tids_ref, valid_ref, offs_ref, o_ref,
                      acc, t=t, bm=bm)


def ftimm_gemm_ragged(
    x: jax.Array,                 # (Tp, Kp) flat rows, padded
    w: jax.Array,                 # (G, Kp, Np) "nn" | (G, Np, Kp) "nt"
    group_ids: jax.Array,         # (NT,) int32 — visit list (scalar prefetch)
    tile_ids: jax.Array,          # (NT,) int32
    valid: jax.Array,             # (NT,) int32 0/1
    group_offsets: jax.Array,     # (G+1,) int32 prefix sums, offsets[G] == T
    *,
    bm: int,
    bn: int,
    bk: int,
    trans: str = "nn",
    out_dtype=None,
    interpret: bool = False,
    epilogue: Epilogue = IDENTITY,
    bias: jax.Array | None = None,
    scale: jax.Array | None = None,
) -> jax.Array:
    """Ragged grouped GEMM: per-group row chunks against per-group panels.

    Grid is (N/bn, NT, K/bk): N outermost so consecutive visits of a shared
    row tile keep the same output block resident (the masked-store protocol
    above); K innermost revisits the accumulator as in ``ftimm_gemm``.
    ``trans`` transposes the per-group panel: "nn" contracts panel rows,
    "nt" panel columns (the dX backward of the "nn" forward).

    ``epilogue`` supports the per-expert operands: ``bias`` (G, N) and the
    dequant ``scale`` vector (G, N) are indexed by the visit list's group id
    and applied at the flush (residual is not supported here — the RMW
    store would double-add it on shared tiles).  Mixed float x int operands
    dequantize at load (int8 expert panels under bf16 tokens).
    """
    tp, kp = x.shape
    out_dtype = out_dtype or x.dtype
    assert not epilogue.residual, "ragged kernel has no residual operand"
    if trans == "nn":
        _, kp_w, np_ = w.shape
        dims = ((1,), (0,))
        w_spec = pl.BlockSpec(
            (1, bk, bn), lambda j, t, k, g_r, t_r, v_r, o_r: (g_r[t], k, j))
    elif trans == "nt":
        _, np_, kp_w = w.shape
        dims = ((1,), (1,))
        w_spec = pl.BlockSpec(
            (1, bn, bk), lambda j, t, k, g_r, t_r, v_r, o_r: (g_r[t], j, k))
    else:
        raise ValueError(trans)
    assert kp_w == kp and tp % bm == 0 and kp % bk == 0 and np_ % bn == 0, (
        x.shape, w.shape, bm, bn, bk)
    nt = group_ids.shape[0]
    gk = kp // bk
    num_groups = group_offsets.shape[0] - 1
    x_spec = pl.BlockSpec(
        (bm, bk), lambda j, t, k, g_r, t_r, v_r, o_r: (t_r[t], k))
    o_spec = pl.BlockSpec(
        (bm, bn), lambda j, t, k, g_r, t_r, v_r, o_r: (t_r[t], j))
    # Per-expert (N,)-wide epilogue operand: one row per group, indexed by
    # the visit's group id exactly like the weight panel.
    vec_spec = pl.BlockSpec(
        (1, 1, bn), lambda j, t, k, g_r, t_r, v_r, o_r: (g_r[t], 0, j))
    in_specs, inputs = [x_spec, w_spec], [x, w]
    if epilogue.bias:
        assert bias.shape == (num_groups, np_), (bias.shape, w.shape)
        in_specs.append(vec_spec)
        inputs.append(bias.reshape(num_groups, 1, np_))
    if epilogue.scale_vec:
        assert scale.shape == (num_groups, np_), (scale.shape, w.shape)
        in_specs.append(vec_spec)
        inputs.append(scale.reshape(num_groups, 1, np_).astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_ragged_kernel, nk=gk, bm=bm, dims=dims,
                          epi=epilogue),
        grid_spec=prefetch_scalar_grid_spec(
            num_scalar_prefetch=4,
            grid=(np_ // bn, nt, gk),
            in_specs=in_specs,
            out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((bm, bn),
                                       _acc_dtype(x.dtype, w.dtype))],
        ),
        out_shape=jax.ShapeDtypeStruct((tp, np_), out_dtype),
        compiler_params=_compiler_params(
            "arbitrary", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(group_ids, tile_ids, valid, group_offsets, *inputs)


def _ragged_swiglu_kernel(gids_ref, tids_ref, valid_ref, offs_ref,
                          x_ref, wg_ref, wu_ref, o_ref,
                          accg_ref, accu_ref, *, nk, bm):
    t, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x_blk = x_ref[...]
    accg_ref[...] += jax.lax.dot_general(
        x_blk, wg_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    accu_ref[...] += jax.lax.dot_general(
        x_blk, wu_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        gate = accg_ref[...]
        act = gate * jax.nn.sigmoid(gate) * accu_ref[...]
        _ragged_store(gids_ref, tids_ref, valid_ref, offs_ref, o_ref,
                      act, t=t, bm=bm)


def ftimm_gemm_ragged_swiglu(
    x: jax.Array,                 # (Tp, Kp)
    w_gate: jax.Array,            # (G, Kp, Np)
    w_up: jax.Array,              # (G, Kp, Np)
    group_ids: jax.Array,
    tile_ids: jax.Array,
    valid: jax.Array,
    group_offsets: jax.Array,
    *,
    bm: int,
    bn: int,
    bk: int,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Ragged grouped GEMM pair with fused silu(x@Wg) * (x@Wu) epilogue.

    One kernel launch for the MoE gate/up projections: both panels stream
    against the same x tile (one fetch of x per step instead of two), two
    fp32 accumulators ride the K loop, and the SwiGLU nonlinearity is applied
    in VMEM at the flush — the epilogue fusion the grouped subsystem's
    ROADMAP entry called for."""
    tp, kp = x.shape
    out_dtype = out_dtype or x.dtype
    _, kp_w, np_ = w_gate.shape
    assert w_up.shape == w_gate.shape and kp_w == kp, (w_gate.shape, w_up.shape)
    assert tp % bm == 0 and kp % bk == 0 and np_ % bn == 0, (
        x.shape, w_gate.shape, bm, bn, bk)
    nt = group_ids.shape[0]
    gk = kp // bk
    x_spec = pl.BlockSpec(
        (bm, bk), lambda j, t, k, g_r, t_r, v_r, o_r: (t_r[t], k))
    w_spec = pl.BlockSpec(
        (1, bk, bn), lambda j, t, k, g_r, t_r, v_r, o_r: (g_r[t], k, j))
    o_spec = pl.BlockSpec(
        (bm, bn), lambda j, t, k, g_r, t_r, v_r, o_r: (t_r[t], j))
    return pl.pallas_call(
        functools.partial(_ragged_swiglu_kernel, nk=gk, bm=bm),
        grid_spec=prefetch_scalar_grid_spec(
            num_scalar_prefetch=4,
            grid=(np_ // bn, nt, gk),
            in_specs=[x_spec, w_spec, w_spec],
            out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                            pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tp, np_), out_dtype),
        compiler_params=_compiler_params(
            "arbitrary", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(group_ids, tile_ids, valid, group_offsets, x, w_gate, w_up)


def _ragged_dw_kernel(gids_ref, tids_ref, valid_ref, offs_ref,
                      x_ref, dy_ref, o_ref, acc_ref, *, nt, bm):
    t = pl.program_id(2)
    g = gids_ref[t]
    first = (t == 0) | (g != gids_ref[jnp.maximum(t - 1, 0)])
    last = (t == nt - 1) | (g != gids_ref[jnp.minimum(t + 1, nt - 1)])

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x_blk = x_ref[...]
    mask = _ragged_row_mask(offs_ref, g, tids_ref[t], valid_ref[t],
                            x_blk.shape, bm)
    x_blk = jnp.where(mask, x_blk, jnp.zeros_like(x_blk))
    acc_ref[...] += jax.lax.dot_general(
        x_blk, dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def ftimm_gemm_ragged_dw(
    x: jax.Array,                 # (Tp, Dp) padded rows
    dy: jax.Array,                # (Tp, Fp)
    group_ids: jax.Array,
    tile_ids: jax.Array,
    valid: jax.Array,
    group_offsets: jax.Array,
    *,
    bm: int,                      # D-dim block (output rows)
    bn: int,                      # F-dim block (output cols)
    bk: int,                      # ragged row-tile size (contraction)
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Ragged T2 grouped GEMM: dW[g] = x[rows_g].T @ dy[rows_g] -> (G, D, F).

    The ragged dimension is now the *contraction* (the paper's T2 regime,
    K = tokens >> M ~ N, per group).  Grid is (D/bm, F/bn, NT) with the visit
    list innermost: visits of one group are contiguous, so the fp32
    accumulator integrates that group's row tiles and flushes once per group;
    boundary tiles mask foreign rows on the *input* side (zeroed before the
    dot) since the contraction admits no output-side masking.  Metadata
    forces one visit per empty group, whose flush stores the zero panel."""
    tp, dp = x.shape
    tp2, fp = dy.shape
    out_dtype = out_dtype or x.dtype
    assert tp2 == tp and tp % bk == 0 and dp % bm == 0 and fp % bn == 0, (
        x.shape, dy.shape, bm, bn, bk)
    num_groups = group_offsets.shape[0] - 1
    nt = group_ids.shape[0]
    x_spec = pl.BlockSpec(
        (bk, bm), lambda i, j, t, g_r, t_r, v_r, o_r: (t_r[t], i))
    dy_spec = pl.BlockSpec(
        (bk, bn), lambda i, j, t, g_r, t_r, v_r, o_r: (t_r[t], j))
    o_spec = pl.BlockSpec(
        (1, bm, bn), lambda i, j, t, g_r, t_r, v_r, o_r: (g_r[t], i, j))
    return pl.pallas_call(
        functools.partial(_ragged_dw_kernel, nt=nt, bm=bk),
        grid_spec=prefetch_scalar_grid_spec(
            num_scalar_prefetch=4,
            grid=(dp // bm, fp // bn, nt),
            in_specs=[x_spec, dy_spec],
            out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, dp, fp), out_dtype),
        compiler_params=_compiler_params(
            "arbitrary", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(group_ids, tile_ids, valid, group_offsets, x, dy)


def _splitk_kernel(a_ref, b_ref, c_ref, acc_ref, *, nk, dims, gk, bk,
                   k_total, mask_k):
    s, k = pl.program_id(0), pl.program_id(3)
    k_lim = _k_limit(k_total, bk, s * gk + k) if mask_k else None
    _accum_body(a_ref[...], b_ref[...], c_ref.at[0], acc_ref,
                k=k, nk=nk, dims=dims, k_lim=k_lim)


def ftimm_gemm_splitk(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int,
    bn: int,
    bk: int,
    nsplit: int,
    trans: str = "nn",
    out_dtype=None,
    interpret: bool = False,
    epilogue: Epilogue = IDENTITY,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
) -> jax.Array:
    """K-parallel ftIMM GEMM (paper Alg. 5).

    Returns the REDUCED (M, N) result; the partials buffer (nsplit, M, N)
    — fp32, or int32 on the int x int path — is produced by the kernel and
    summed outside it, the TPU analogue of the paper's reduction of
    per-core partial C through GSM.  K need not divide into nsplit *
    bk-multiples: each split owns ``cdiv(cdiv(K, bk), nsplit)`` K blocks
    and out-of-range blocks mask to zero contributions.  The epilogue
    applies AFTER the reduction (its activation is nonlinear, so per-split
    flushing would be wrong; the LINEAR dequant ``scale`` vector commutes
    with the sum, so applying it post-reduction is exact) — still one fused
    elementwise pass over the partial sum, not per-op XLA passes over a
    stored output.
    """
    m, k, n = _mkn(trans, a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    nkb = pl.cdiv(k, bk)                 # total K blocks over the real K
    gk = pl.cdiv(nkb, nsplit)            # K blocks per split
    mask_k = bool(k % bk) or bool(nkb % nsplit)
    gm, gn = pl.cdiv(m, bm), pl.cdiv(n, bn)
    dims = _DIMS[trans]

    # Index maps: split s owns K blocks [s*gk, (s+1)*gk).
    if trans == "nn":
        a_spec = pl.BlockSpec((bm, bk), lambda s, i, j, k: (i, s * gk + k))
        b_spec = pl.BlockSpec((bk, bn), lambda s, i, j, k: (s * gk + k, j))
    elif trans == "tn":
        a_spec = pl.BlockSpec((bk, bm), lambda s, i, j, k: (s * gk + k, i))
        b_spec = pl.BlockSpec((bk, bn), lambda s, i, j, k: (s * gk + k, j))
    else:  # nt
        a_spec = pl.BlockSpec((bm, bk), lambda s, i, j, k: (i, s * gk + k))
        b_spec = pl.BlockSpec((bn, bk), lambda s, i, j, k: (j, s * gk + k))
    c_spec = pl.BlockSpec((1, bm, bn), lambda s, i, j, k: (s, i, j))

    acc_dtype = _acc_dtype(a.dtype, b.dtype)
    partials = pl.pallas_call(
        functools.partial(_splitk_kernel, nk=gk, dims=dims, gk=gk, bk=bk,
                          k_total=k, mask_k=mask_k),
        grid=(nsplit, gm, gn, gk),
        in_specs=[a_spec, b_spec],
        out_specs=c_spec,
        out_shape=jax.ShapeDtypeStruct((nsplit, m, n), acc_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=_compiler_params(
            "parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(a, b)
    out = jnp.sum(partials, axis=0)
    if not epilogue.is_identity:
        out = epilogue.apply(out.astype(jnp.float32), bias=bias,
                             residual=residual, scale=scale)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Fused silu(x@Wg) * (x@Wu) pair — the dense/grouped two-output epilogue
# variant mirroring the ragged ftimm_gemm_ragged_swiglu: both panels stream
# against the same x tile (one fetch of x per step instead of two), two fp32
# accumulators ride the K loop, and the SwiGLU nonlinearity is applied in
# VMEM at the flush.  One kernel launch for a dense MLP's gate/up pair.
# ---------------------------------------------------------------------------


def _swiglu_body(x_blk, wg_blk, wu_blk, o_ref, accg_ref, accu_ref, *,
                 k, nk, k_lim):
    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    if k_lim is not None:
        x_blk = _mask_contract(x_blk, k_lim, 1)
        wg_blk = _mask_contract(wg_blk, k_lim, 0)
        wu_blk = _mask_contract(wu_blk, k_lim, 0)
    accg_ref[...] += jax.lax.dot_general(
        x_blk, wg_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    accu_ref[...] += jax.lax.dot_general(
        x_blk, wu_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        gate = accg_ref[...]
        act = gate * jax.nn.sigmoid(gate) * accu_ref[...]
        o_ref[...] = act.astype(o_ref.dtype)


def _swiglu_kernel(x_ref, wg_ref, wu_ref, o_ref, accg_ref, accu_ref, *,
                   nk, bk, k_total, mask_k):
    k = pl.program_id(2)
    k_lim = _k_limit(k_total, bk, k) if mask_k else None
    _swiglu_body(x_ref[...], wg_ref[...], wu_ref[...], o_ref,
                 accg_ref, accu_ref, k=k, nk=nk, k_lim=k_lim)


def ftimm_gemm_swiglu(
    x: jax.Array,                 # (M, K)
    w_gate: jax.Array,            # (K, N)
    w_up: jax.Array,              # (K, N)
    *,
    bm: int,
    bn: int,
    bk: int,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Dense fused SwiGLU pair: silu(x @ Wg) * (x @ Wu) in ONE kernel launch
    (shapes need not be block multiples — remainder K tiles mask in-kernel).
    """
    m, k = x.shape
    kw, n = w_gate.shape
    assert kw == k and w_up.shape == w_gate.shape, (
        x.shape, w_gate.shape, w_up.shape)
    out_dtype = out_dtype or x.dtype
    gm, gn, gk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk)
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    w_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    return pl.pallas_call(
        functools.partial(_swiglu_kernel, nk=gk, bk=bk, k_total=k,
                          mask_k=bool(k % bk)),
        grid=(gm, gn, gk),
        in_specs=[x_spec, w_spec, w_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, w_gate, w_up)


def _grouped_swiglu_kernel(x_ref, wg_ref, wu_ref, o_ref, accg_ref, accu_ref,
                           *, nk, bk, k_total, mask_k, x_batched):
    k = pl.program_id(3)
    k_lim = _k_limit(k_total, bk, k) if mask_k else None
    x_blk = x_ref[0] if x_batched else x_ref[...]
    _swiglu_body(x_blk, wg_ref[0], wu_ref[0], o_ref.at[0],
                 accg_ref, accu_ref, k=k, nk=nk, k_lim=k_lim)


def ftimm_gemm_grouped_swiglu(
    x: jax.Array,                 # (G, M, K) per-group rows | (M, K) shared
    w_gate: jax.Array,            # (G, K, N)
    w_up: jax.Array,              # (G, K, N)
    *,
    bm: int,
    bn: int,
    bk: int,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Grouped fused SwiGLU pair: silu(x_g @ Wg_g) * (x_g @ Wu_g) per group
    in ONE launch — the capacity-mode MoE gate/up projections
    ``(E, C, D) @ (E, D, F)`` without the separate silu/mul XLA passes.
    ``x`` may be 2-D (shared rows against per-group panels)."""
    x_batched = x.ndim == 3
    g, kw, n = w_gate.shape
    m, k = x.shape[-2:]
    assert kw == k and w_up.shape == w_gate.shape, (
        x.shape, w_gate.shape, w_up.shape)
    if x_batched:
        assert x.shape[0] == g, (x.shape, w_gate.shape)
    out_dtype = out_dtype or x.dtype
    gm, gn, gk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk)
    if x_batched:
        x_spec = pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k))
    else:
        x_spec = pl.BlockSpec((bm, bk), lambda g, i, j, k: (i, k))
    w_spec = pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j))
    o_spec = pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j))
    return pl.pallas_call(
        functools.partial(_grouped_swiglu_kernel, nk=gk, bk=bk, k_total=k,
                          mask_k=bool(k % bk), x_batched=x_batched),
        grid=(g, gm, gn, gk),
        in_specs=[x_spec, w_spec, w_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((g, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_compiler_params(
            "parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, w_gate, w_up)
