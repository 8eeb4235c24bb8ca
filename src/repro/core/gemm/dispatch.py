"""Framework-wide GEMM entry point.

Every dense contraction in the model stack routes through ``matmul`` /
``project``: the shape is classified (paper §III-A), the CMR tuner picks the
strategy + blocks (paper §IV-C), and the call dispatches to

  * the specialized Pallas ftIMM kernel on TPU (or in interpret mode when
    explicitly requested, e.g. kernel tests), wrapped in a custom VJP whose
    backward GEMMs are themselves ftIMM-planned — dW = x.T @ dy is the
    paper's T2 shape and gets the K-oriented treatment automatically;
  * an XLA ``dot_general`` path on CPU (used by the multi-pod dry-run so
    ``cost_analysis`` reflects the true FLOPs/bytes) with identical
    fp32-accumulation semantics.

Backend selection: ``REPRO_GEMM_BACKEND`` env var ("pallas" | "xla" |
"pallas_interpret"), else pallas on TPU and xla elsewhere.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import warnings

import jax
import jax.numpy as jnp

from ...analysis import contracts as _contracts
from ...kernels.ftimm import ops as _ops
from ...kernels.ftimm import ref as _ref
from ...kernels.ftimm.epilogue import IDENTITY, Epilogue
from ...runtime import chaos as _chaos
from .. import quant as _quant
from .tuner import (note_degraded, note_epilogue, note_plan_use,
                    plan_batched_gemm, plan_gemm, plan_ragged_gemm)

_REF = {"nn": _ref.matmul_nn, "tn": _ref.matmul_tn, "nt": _ref.matmul_nt}


# ---------------------------------------------------------------------------
# Dispatch fallback ladder: when a chaos-injected fault (``runtime.chaos``)
# fails a kernel, the call degrades one rung instead of taking the request
# down — pallas -> the XLA oracle with identical fp32-accumulation
# semantics, fused epilogue -> the unfused two-pass spelling.  Only
# ``ChaosError`` is caught: a real Mosaic / lowering / launch error
# propagates, so a kernel that cannot run on the device is never hidden
# behind an XLA serving.  Every degraded serving is counted in
# ``tuner.plan_mode_stats()`` and the first occurrence of each rung is
# logged once.
# ---------------------------------------------------------------------------

_WARNED_RUNGS: set = set()


def _degraded(family: str, rung: str, err: BaseException) -> None:
    """Count one fallback-ladder serving and log the rung's first use."""
    note_degraded(family, rung)
    key = (family, rung)
    if key not in _WARNED_RUNGS:
        _WARNED_RUNGS.add(key)
        warnings.warn(
            f"gemm dispatch degraded: {family} {rung} "
            f"({type(err).__name__}: {err})", RuntimeWarning, stacklevel=3)


def _wide(x: jax.Array) -> jax.Array:
    """Upcast narrow-int (quantized) operands for the XLA oracle rungs —
    values are identical by construction, only the engine changes."""
    return x.astype(jnp.float32) if jnp.dtype(x.dtype).itemsize == 1 else x


def _xla_dense(a: jax.Array, b: jax.Array, trans: str, out_dtype,
               epi: Epilogue = IDENTITY, bias=None, residual=None,
               scale=None) -> jax.Array:
    """The dense XLA oracle rung: fp32-accumulating dot + the epilogue tail
    applied in the same jit (numerically the unfused planned path)."""
    if epi.is_identity:
        return _REF[trans](_wide(a), _wide(b), out_dtype)
    z = _REF[trans](_wide(a), _wide(b), jnp.float32)
    return epi.apply(z, bias=bias, residual=residual,
                     scale=scale).astype(out_dtype)


def _check_epi(epi: Epilogue, bias, residual, scale=None) -> None:
    if epi.bias != (bias is not None):
        raise ValueError(
            f"epilogue.bias={epi.bias} but bias operand "
            f"{'missing' if bias is None else 'given'}")
    if epi.residual != (residual is not None):
        raise ValueError(
            f"epilogue.residual={epi.residual} but residual operand "
            f"{'missing' if residual is None else 'given'}")
    if epi.scale_vec != (scale is not None):
        raise ValueError(
            f"epilogue.scale_vec={epi.scale_vec} but scale operand "
            f"{'missing' if scale is None else 'given'}")


def _backend() -> str:
    env = os.environ.get("REPRO_GEMM_BACKEND")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _verify_enabled() -> bool:
    return os.environ.get("REPRO_VERIFY", "") not in ("", "0")


@functools.lru_cache(maxsize=4096)
def _verify_cached(family: str, dims: tuple, plan, in_bytes: int,
                   out_bytes: int, epi, swiglu: bool, ragged: str,
                   trans: str, b_bytes: int | None = None) -> bool:
    _contracts.assert_plan(family, dims, plan, in_bytes=in_bytes,
                           out_bytes=out_bytes, epilogue=epi, swiglu=swiglu,
                           ragged=ragged, trans=trans, b_bytes=b_bytes,
                           coverage=family in ("dense", "batched"))
    return True


def _verify(family: str, dims, plan, in_bytes: int, out_bytes: int, *,
            epi=None, swiglu: bool = False, ragged: str = "m",
            trans: str = "nn", b_bytes: int | None = None) -> None:
    """``REPRO_VERIFY=1`` mode: assert the static kernel contracts
    (``analysis.contracts.check_plan`` incl. the symbolic store-coverage
    proof) on every planned call, raising ``analysis.ContractError`` before
    any kernel is launched.  Trace-time only; results are memoized per
    (shape, plan) so steady-state dispatch cost is one env read."""
    if _verify_enabled():
        _verify_cached(family, tuple(int(d) for d in dims), plan,
                       int(in_bytes), int(out_bytes), epi, swiglu, ragged,
                       trans, None if b_bytes is None else int(b_bytes))


def _check_vectors(family: str, dims, epi: Epilogue, bias, scale) -> None:
    """Raise ``ContractError`` on a malformed flush-vector operand (wrong N,
    neither shared (N,) nor per-expert (G, N)) — always on, trace-time."""
    if bias is None and scale is None:
        return
    bad = _contracts.errors(_contracts.check_epilogue_vectors(
        family, dims, epi,
        bias_shape=None if bias is None else bias.shape,
        scale_shape=None if scale is None else scale.shape))
    if bad:
        raise _contracts.ContractError(bad,
                                       context=f"{family}{tuple(dims)}")


def _b_bytes(a: jax.Array, b: jax.Array) -> int | None:
    """The planners' dtype-axis key: B's element width when it differs from
    A's (the weight-only mixed paths), else None (homogeneous — legacy
    keys)."""
    bb = jnp.dtype(b.dtype).itemsize
    return None if bb == jnp.dtype(a.dtype).itemsize else bb


def _plan_trans(trans: str) -> str:
    """The layout the planners key on: only "tn" (M on A's lane axis)
    changes the legal tilings, so "nt" shares the "nn" plans."""
    return "tn" if trans == "tn" else "nn"


def _mkn(trans: str, a_shape, b_shape):
    if trans == "nn":
        (m, k), (_, n) = a_shape, b_shape
    elif trans == "tn":
        (k, m), (_, n) = a_shape, b_shape
    else:
        (m, k), (n, _) = a_shape, b_shape
    return m, k, n


def _run_planned(a: jax.Array, b: jax.Array, trans: str, out_dtype,
                 interpret: bool, epi: Epilogue = IDENTITY,
                 bias=None, residual=None, scale=None) -> jax.Array:
    m, k, n = _mkn(trans, a.shape, b.shape)
    in_bytes = jnp.dtype(a.dtype).itemsize
    out_bytes = jnp.dtype(out_dtype).itemsize
    bb = _b_bytes(a, b)
    plan = plan_gemm(m, k, n, in_bytes, out_bytes, epi_ops=epi.num_ops,
                     b_bytes=bb, trans=_plan_trans(trans),
                     epilogue=None if epi.is_identity else epi)
    _verify("dense", (m, k, n), plan, in_bytes, out_bytes, epi=epi,
            trans=trans, b_bytes=bb)
    note_plan_use("dense", plan)
    if epi.is_identity:
        try:
            _chaos.fire("kernel")
            return _ops.gemm(
                a, b, trans=trans, out_dtype=out_dtype, interpret=interpret,
                **plan.kernel_kwargs(),
            )
        except _chaos.ChaosError as e:
            _degraded("dense", "pallas->xla", e)
            return _xla_dense(a, b, trans, out_dtype)
    note_epilogue("dense", plan.fuse)
    if plan.fuse:
        try:
            _chaos.fire("kernel_fused")
            return _ops.gemm(
                a, b, trans=trans, out_dtype=out_dtype, interpret=interpret,
                epilogue=epi, bias=bias, residual=residual, scale=scale,
                **plan.kernel_kwargs(),
            )
        except _chaos.ChaosError as e:
            # Fused kernel failed: next rung is the unfused spelling below
            # (identity kernel + separate tail), NOT straight to XLA.
            _degraded("dense", "fused->unfused", e)
    # The plan declined fusion (a measured winner can) or the fused kernel
    # just failed: identity kernel + the tail as its own pass, exactly what
    # the tuner priced.
    try:
        _chaos.fire("kernel")
        z = _ops.gemm(a, b, trans=trans, out_dtype=jnp.float32,
                      interpret=interpret, **plan.kernel_kwargs())
    except _chaos.ChaosError as e:
        _degraded("dense", "pallas->xla", e)
        return _xla_dense(a, b, trans, out_dtype, epi, bias, residual, scale)
    return epi.apply(z, bias=bias, residual=residual,
                     scale=scale).astype(out_dtype)


@functools.lru_cache(maxsize=None)
def _pallas_fn(trans: str, out_dtype_name: str, interpret: bool,
               epi: Epilogue = IDENTITY):
    """Build the custom-VJP'd Pallas matmul for one (trans, dtype, epilogue)
    combo.  ``extras`` is the tuple of present epilogue operands (bias,
    residual and/or scale vector, in that order) so the custom_vjp signature
    stays fixed per spec.  The backward rematerializes the pre-epilogue fp32
    GEMM (the same remat the ragged SwiGLU backward does), pulls the
    elementwise tail's cotangents out with ``jax.vjp`` (exact for every
    activation), and runs the two planned backward GEMMs on the
    pre-activation cotangent."""
    out_dtype = jnp.dtype(out_dtype_name)

    @jax.custom_vjp
    def f(a, b, extras):
        bias, residual, scale = epi.unpack(extras)
        return _run_planned(a, b, trans, out_dtype, interpret, epi,
                            bias, residual, scale)

    def fwd(a, b, extras):
        return f(a, b, extras), (a, b, extras)

    def bwd(res, g):
        a, b, extras = res
        run = lambda x, y, t, dt: _run_planned(x, y, t, dt, interpret)  # noqa: E731
        if epi.is_identity:
            dz, d_extras = g, ()
        else:
            z = run(a, b, trans, jnp.float32)       # remat pre-activation

            def epi_fn(z_, *extras_):
                bias_, residual_, scale_ = epi.unpack(extras_)
                return epi.apply(z_, bias=bias_, residual=residual_,
                                 scale=scale_)

            _, epi_vjp = jax.vjp(epi_fn, z, *extras)
            grads = epi_vjp(g.astype(jnp.float32))
            dz = grads[0].astype(a.dtype)
            d_extras = tuple(d.astype(x.dtype)
                             for d, x in zip(grads[1:], extras))
        if trans == "nn":          # y = a @ b
            da = run(dz, b, "nt", a.dtype)
            db = run(a, dz, "tn", b.dtype)  # T2: K = tokens >> M ~ N
        elif trans == "tn":        # y = a.T @ b, a: (K, M)
            da = run(b, dz, "nt", a.dtype)  # (K,N)@(N,M) -> (K,M)
            db = run(a, dz, "nn", b.dtype)  # (K,M)@(M,N) -> (K,N)
        else:                      # y = a @ b.T, b: (N, K)
            da = run(dz, b, "nn", a.dtype)  # (M,N)@(N,K) -> (M,K)
            db = run(dz, a, "tn", b.dtype)  # g.T @ a -> (N,K)
        return da, db, d_extras

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _quant_fn(qcfg: "_quant.QuantConfig", trans: str, out_dtype_name: str,
              backend: str, epi: Epilogue = IDENTITY):
    """Custom-VJP'd quantized dense matmul for one (quant config, dtype,
    backend, epilogue) combo — the managed ``matmul(..., quant=)`` engine.

    Forward quantizes IN-TRACE (weights per channel, activations per tensor
    for the dynamic modes — under jit with frozen weights the weight
    quantization constant-folds) and runs the narrow-/mixed-dtype planned
    GEMM with the combined dequant vector fused at the accumulator flush
    (``scale_vec``), then the caller's epilogue tail.  Serving paths that
    want zero per-call quantization cost pre-quantize with
    ``core.quant.quantize_weights`` and call ``matmul`` with int8 weights +
    an ``epilogue.scale_vec`` spec directly.

    Backward is straight-through against the DEQUANTIZED weights: d_a is the
    planned "nt" product of the (per-channel-rescaled) cotangent against the
    int8 panel — algebraically dz @ dequant(W).T — and d_b is the
    full-precision T2 product, so quantization noise perturbs the forward
    values, never the gradient estimator."""
    out_dtype = jnp.dtype(out_dtype_name)
    interpret = backend == "pallas_interpret"
    qepi = dataclasses.replace(epi, scale_vec=True)

    def quantize_operands(a, b):
        """(a_run, w_q, w_scale, combined_flush_scale)."""
        w_q, w_scale = _quant.quantize_weights(b, qcfg)
        if qcfg.mode == "w4":
            # Round-trip the nibble packing: the kernel consumes int8, but
            # values must be exactly what the packed storage format holds.
            w_q = _quant.unpack_int4(_quant.pack_int4(w_q))
        if qcfg.weight_only:
            return a, w_q, w_scale, w_scale
        a_q, a_scale = _quant.quantize_activations(a, qcfg)
        return a_q, w_q, w_scale, w_scale * a_scale

    def gemm32(x, y, t):
        """Planned fp32-out product that tolerates narrow/mixed operands on
        every backend (the XLA engine upcasts explicitly)."""
        if backend == "xla":
            return _REF[t](x.astype(jnp.float32), y.astype(jnp.float32),
                           jnp.float32)
        return _run_planned(x, y, t, jnp.float32, interpret)

    @jax.custom_vjp
    def f(a, b, extras):
        bias, residual, _ = epi.unpack(extras)
        a_q, w_q, _w_scale, sv = quantize_operands(a, b)
        if backend == "xla":
            m, k, n = _mkn(trans, a.shape, b.shape)
            in_bytes = jnp.dtype(a_q.dtype).itemsize
            plan = plan_gemm(m, k, n, in_bytes, out_dtype.itemsize,
                             epi_ops=qepi.num_ops,
                             b_bytes=_b_bytes(a_q, w_q), epilogue=qepi)
            note_plan_use("dense", plan)
            note_epilogue("dense", True)
            z = _REF[trans](a_q.astype(jnp.float32),
                            w_q.astype(jnp.float32), jnp.float32)
            return qepi.apply(z, bias=bias, residual=residual,
                              scale=sv).astype(out_dtype)
        return _run_planned(a_q, w_q, trans, out_dtype, interpret, qepi,
                            bias, residual, sv)

    def fwd(a, b, extras):
        return f(a, b, extras), (a, b, extras)

    def bwd(res, g):
        a, b, extras = res
        a_q, w_q, w_scale, sv = quantize_operands(a, b)
        if epi.is_identity:
            dz, d_extras = g.astype(jnp.float32), ()
        else:
            # Remat the pre-tail value the forward produced (dequantized
            # GEMM output) and pull the tail's cotangents out exactly.
            z = gemm32(a_q, w_q, trans) * sv.astype(jnp.float32)

            def epi_fn(z_, *extras_):
                bias_, residual_, _ = epi.unpack(extras_)
                return epi.apply(z_, bias=bias_, residual=residual_)

            _, epi_vjp = jax.vjp(epi_fn, z, *extras)
            grads = epi_vjp(g.astype(jnp.float32))
            dz = grads[0]
            d_extras = tuple(d.astype(x.dtype)
                             for d, x in zip(grads[1:], extras))
        # dz @ dequant(W).T == (dz * w_scale) @ W_q.T — the per-channel
        # scale folds into the cotangent's columns, so the backward GEMM
        # streams the narrow panel too.
        da = gemm32((dz * w_scale.astype(jnp.float32)).astype(a.dtype),
                    w_q, "nt").astype(a.dtype)
        db = gemm32(a, dz.astype(a.dtype), "tn").astype(b.dtype)
        return da, db, d_extras

    f.defvjp(fwd, bwd)
    return f


def matmul(a: jax.Array, b: jax.Array, *, trans: str = "nn",
           out_dtype=None, backend: str | None = None,
           epilogue: Epilogue | None = None,
           bias: jax.Array | None = None,
           residual: jax.Array | None = None,
           scale: jax.Array | None = None,
           quant: "_quant.QuantConfig | str | None" = None) -> jax.Array:
    """2-D GEMM through the ftIMM planner. fp32 accumulation always.

    ``epilogue`` fuses the elementwise tail (bias add / activation /
    residual add / scale, ``kernels.ftimm.Epilogue``) into the accumulator
    flush on the Pallas path — and into the same jit on the XLA fallback, so
    CPU/TPU stay comparable — instead of separate XLA passes over the stored
    output.  ``bias`` is (N,), ``residual`` (M, N); both differentiable.

    ``scale`` is the (N,)-wide fp32 dequant vector of a
    ``epilogue.scale_vec`` spec — the manual spelling for callers holding
    PRE-quantized operands (int8/fp8 ``a``/``b`` from
    ``core.quant.quantize_weights``): the raw (integer) accumulator is
    multiplied by it at the flush.  ``quant`` is the managed spelling: a
    ``core.quant.QuantConfig`` (or mode string — "w8" / "w4" / "int8" /
    "fp8_e4m3" / "fp8_e5m2") quantizing full-precision operands in-trace and
    wrapping the whole thing in a straight-through custom VJP (backward runs
    bf16/fp32 against the dequantized weights)."""
    epi = IDENTITY if epilogue is None else epilogue
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    backend = backend or _backend()
    if backend not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown gemm backend: {backend}")
    qcfg = _quant.resolve(quant)
    if not qcfg.is_noop:
        if trans != "nn":
            raise ValueError("quantized matmul is defined for trans='nn' "
                             f"only (got trans={trans!r})")
        if epi.scale_vec or scale is not None:
            raise ValueError(
                "quant= derives its own dequant scale; for manual control "
                "pass pre-quantized operands with epilogue.scale_vec "
                "instead")
        _check_epi(epi, bias, residual)
        _check_vectors("dense", _mkn(trans, a.shape, b.shape), epi, bias,
                       None)
        extras = tuple(x for x in (bias, residual) if x is not None)
        return _quant_fn(qcfg, trans, out_dtype.name, backend,
                         epi)(a, b, extras)
    _check_epi(epi, bias, residual, scale)
    _check_vectors("dense", _mkn(trans, a.shape, b.shape), epi, bias, scale)
    if backend == "xla":
        # Plan even though XLA ignores the blocks: keeps the plan cache an
        # accurate census of the workload's shapes (as the batched/ragged
        # paths already do) and the mode telemetry complete.
        m, k, n = _mkn(trans, a.shape, b.shape)
        in_bytes = jnp.dtype(a.dtype).itemsize
        bb = _b_bytes(a, b)
        plan = plan_gemm(m, k, n, in_bytes, out_dtype.itemsize,
                         epi_ops=epi.num_ops, b_bytes=bb,
                         trans=_plan_trans(trans),
                         epilogue=None if epi.is_identity else epi)
        _verify("dense", (m, k, n), plan, in_bytes, out_dtype.itemsize,
                epi=epi, trans=trans, b_bytes=bb)
        note_plan_use("dense", plan)
        if epi.is_identity:
            return _REF[trans](a, b, out_dtype)
        note_epilogue("dense", True)    # one jit: XLA fuses the tail
        z = _REF[trans](a, b, jnp.float32)
        return epi.apply(z, bias=bias, residual=residual,
                         scale=scale).astype(out_dtype)
    extras = tuple(x for x in (bias, residual, scale) if x is not None)
    return _pallas_fn(trans, out_dtype.name,
                      backend == "pallas_interpret", epi)(a, b, extras)


def _ref_batched(a: jax.Array, b: jax.Array, trans: str,
                 out_dtype) -> jax.Array:
    """XLA oracle for batched/grouped GEMM with fp32 accumulation.  Either
    operand may be 2-D (shared across the batch)."""
    al = "gmk" if a.ndim == 3 else "mk"
    bl = "gkn" if b.ndim == 3 else "kn"
    if trans == "tn":
        al = al.replace("mk", "km")
    elif trans == "nt":
        bl = bl.replace("kn", "nk")
    elif trans != "nn":
        raise ValueError(trans)
    out = jnp.einsum(f"{al},{bl}->gmn", a, b,
                     preferred_element_type=jnp.float32)
    return out.astype(out_dtype)


def _batched_mkns(trans: str, a: jax.Array, b: jax.Array):
    m, k, n = _mkn(trans, a.shape[-2:], b.shape[-2:])
    shared = "a" if a.ndim == 2 else ("b" if b.ndim == 2 else "none")
    g = b.shape[0] if shared == "a" else a.shape[0]
    return g, m, k, n, shared


def _run_planned_batched(a: jax.Array, b: jax.Array, trans: str, out_dtype,
                         backend: str) -> jax.Array:
    """Plan one batched/grouped GEMM and run it on the selected backend.

    The planner runs on EVERY backend (it is trace-time-only work and keeps
    the plan cache an accurate census of the workload's irregular shapes);
    only the execution engine differs: XLA dot_general on CPU, the batched
    Pallas kernel on TPU / in interpret mode."""
    g, m, k, n, shared = _batched_mkns(trans, a, b)
    in_bytes = jnp.dtype(a.dtype).itemsize
    out_bytes = jnp.dtype(out_dtype).itemsize
    plan = plan_batched_gemm(g, m, k, n, in_bytes, out_bytes, shared,
                             trans=_plan_trans(trans))
    _verify("batched", (g, m, k, n), plan, in_bytes, out_bytes, trans=trans)
    note_plan_use("batched", plan)
    if backend == "xla":
        return _ref_batched(a, b, trans, out_dtype)
    try:
        _chaos.fire("kernel")
        return _ops.batched_gemm(
            a, b, bm=plan.bm, bn=plan.bn, bk=plan.bk,
            dim_order=plan.dim_order, trans=trans, out_dtype=out_dtype,
            edge=plan.edge, interpret=(backend == "pallas_interpret"),
        )
    except _chaos.ChaosError as e:
        _degraded("batched", "pallas->xla", e)
        return _ref_batched(_wide(a), _wide(b), trans, out_dtype)


@functools.lru_cache(maxsize=None)
def _batched_fn(trans: str, out_dtype_name: str, backend: str):
    """Custom-VJP'd batched matmul for one (trans, dtype, backend) combo.

    Both backward GEMMs are themselves planned batched GEMMs: for the
    grouped MoE forward (E, C, D) @ (E, D, F), dW = x^T dy contracts the
    capacity dim — the paper's T2 shape per expert — and dx is the N<=128
    "nt" GEMM; routing them through ``_run_planned_batched`` is what makes
    the backward pass see the CMR tuner at all."""
    out_dtype = jnp.dtype(out_dtype_name)

    @jax.custom_vjp
    def f(a, b):
        return _run_planned_batched(a, b, trans, out_dtype, backend)

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        run = lambda x, y, t, dt: _run_planned_batched(  # noqa: E731
            x, y, t, dt, backend)
        if trans == "nn":          # y_g = a_g @ b_g
            da = run(g, b, "nt", a.dtype)
            if b.ndim == 2:
                # Shared weight: dW = sum_g x_g^T dy_g == ONE flat T2 GEMM
                # over all G*M rows — no (G, K, N) intermediate.
                return da, matmul(
                    a.reshape(-1, a.shape[-1]), g.reshape(-1, g.shape[-1]),
                    trans="tn", out_dtype=b.dtype, backend=backend)
            db = run(a, g, "tn", b.dtype)   # T2 per group: K = capacity
        elif trans == "tn":        # y_g = a_g.T @ b_g, a: (G, K, M)
            da = run(b, g, "nt", a.dtype)
            db = run(a, g, "nn", b.dtype)
        else:                      # y_g = a_g @ b_g.T, b: (G, N, K)
            da = run(g, b, "nn", a.dtype)
            db = run(g, a, "tn", b.dtype)
        if a.ndim == 2:            # shared a: gradients sum over the batch
            da = jnp.sum(da, axis=0).astype(a.dtype)
        if b.ndim == 2:
            db = jnp.sum(db, axis=0).astype(b.dtype)
        return da, db

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _batched_bias_fn(out_dtype_name: str, backend: str):
    """Custom-VJP'd batched matmul ("nn" only) with the bias epilogue: bias
    is shared (N,) or per-group (G, N), added at each group's accumulator
    flush.  d_bias sums the cotangent over the fused dims (batch + rows for
    shared, rows for per-group)."""
    out_dtype = jnp.dtype(out_dtype_name)
    epi = Epilogue(bias=True)

    @jax.custom_vjp
    def f(a, b, bias):
        g, m, k, n, shared = _batched_mkns("nn", a, b)
        in_bytes = jnp.dtype(a.dtype).itemsize
        plan = plan_batched_gemm(g, m, k, n, in_bytes, out_dtype.itemsize,
                                 shared, epi_ops=epi.num_ops, epilogue=epi)
        _verify("batched", (g, m, k, n), plan, in_bytes, out_dtype.itemsize,
                epi=epi)
        note_plan_use("batched", plan)
        if backend == "xla":
            note_epilogue("batched", True)  # one jit: XLA fuses the tail
            z = _ref_batched(a, b, "nn", jnp.float32)
            bb = bias if bias.ndim == 1 else bias[:, None, :]
            return epi.apply(z, bias=bb).astype(out_dtype)
        note_epilogue("batched", plan.fuse)
        if plan.fuse:
            return _ops.batched_gemm(
                a, b, bm=plan.bm, bn=plan.bn, bk=plan.bk,
                dim_order=plan.dim_order, trans="nn", out_dtype=out_dtype,
                edge=plan.edge, interpret=(backend == "pallas_interpret"),
                epilogue=epi, bias=bias)
        z = _run_planned_batched(a, b, "nn", jnp.float32, backend)
        bb = bias if bias.ndim == 1 else bias[:, None, :]
        return epi.apply(z, bias=bb).astype(out_dtype)

    def fwd(a, b, bias):
        return f(a, b, bias), (a, b, bias)

    def bwd(res, g):
        a, b, bias = res
        run = lambda x, y, t, dt: _run_planned_batched(  # noqa: E731
            x, y, t, dt, backend)
        da = run(g, b, "nt", a.dtype)
        if a.ndim == 2:
            da = jnp.sum(da, axis=0).astype(a.dtype)
        if b.ndim == 2:
            # Shared weight: ONE flat T2 GEMM over all G*M rows.
            db = matmul(a.reshape(-1, a.shape[-1]), g.reshape(-1, g.shape[-1]),
                        trans="tn", out_dtype=b.dtype, backend=backend)
        else:
            db = run(a, g, "tn", b.dtype)
        g32 = g.astype(jnp.float32)
        dbias = (g32.sum(axis=(0, 1)) if bias.ndim == 1
                 else g32.sum(axis=1)).astype(bias.dtype)
        return da, db, dbias

    f.defvjp(fwd, bwd)
    return f


def batched_matmul(a: jax.Array, b: jax.Array, *, trans: str = "nn",
                   out_dtype=None, backend: str | None = None,
                   bias: jax.Array | None = None) -> jax.Array:
    """Batched GEMM (G, M, K) @ (G, K, N) -> (G, M, N) through the ftIMM
    planner; fp32 accumulation always.  Either operand may be 2-D (shared
    across the batch).  The attention BMMs flatten their (batch, kv-head)
    dims into G and route here instead of raw einsum.

    ``bias`` — (N,) shared or (G, N) per-group, added at the accumulator
    flush (trans="nn" only); fully differentiable."""
    assert a.ndim == 3 or b.ndim == 3, (a.shape, b.shape)
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    backend = backend or _backend()
    if backend not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown gemm backend: {backend}")
    if bias is not None:
        if trans != "nn":
            raise ValueError("batched bias epilogue is defined for "
                             f"trans='nn' only (got trans={trans!r})")
        g, m, k, n, _ = _batched_mkns(trans, a, b)
        _check_vectors("batched", (g, m, k, n), Epilogue(bias=True), bias,
                       None)
        return _batched_bias_fn(out_dtype.name, backend)(a, b, bias)
    return _batched_fn(trans, out_dtype.name, backend)(a, b)


def grouped_matmul(x: jax.Array, w: jax.Array, *, trans: str = "nn",
                   out_dtype=None, backend: str | None = None) -> jax.Array:
    """Grouped GEMM: per-group panels where one operand may be shared —
    the MoE expert projections (E, C, D) @ (E, D, F) -> (E, C, F).  Same
    engine as ``batched_matmul``; kept as a distinct entry point so call
    sites read as what they are (experts, not batches)."""
    return batched_matmul(x, w, trans=trans, out_dtype=out_dtype,
                          backend=backend)


# ---------------------------------------------------------------------------
# Fused dense / grouped SwiGLU pairs — one kernel launch for gate + up +
# silu(gate)*up, mirroring the ragged ragged_swiglu entry point.
# ---------------------------------------------------------------------------

def _swiglu_bwd_products(run, x, wg, wu, a, b, g):
    """Shared SwiGLU backward: given the rematerialized fp32 pre-activations
    ``a = x@Wg`` / ``b = x@Wu`` and the output cotangent ``g``, produce
    (dx, dwg, dwu) with every GEMM planned through ``run(x, y, trans,
    out_dtype)``."""
    sg = jax.nn.sigmoid(a)
    g32 = g.astype(jnp.float32)
    da = (g32 * b * sg * (1.0 + a * (1.0 - sg))).astype(x.dtype)
    db = (g32 * a * sg).astype(x.dtype)
    dx = (run(da, wg, "nt", jnp.float32)
          + run(db, wu, "nt", jnp.float32)).astype(x.dtype)
    dwg = run(x, da, "tn", wg.dtype)
    dwu = run(x, db, "tn", wu.dtype)
    return dx, dwg, dwu


def _make_swiglu_fn(out_dtype, backend: str, family: str, plan_fn, run_fn,
                    fused_kernel):
    """Shared custom-VJP scaffolding for the fused SwiGLU pairs.

    ``plan_fn(x, wg)`` plans + records telemetry, ``run_fn(p, q, trans,
    out_dtype)`` is the family's planned GEMM for the unfused forward and
    every backward product, ``fused_kernel(x, wg, wu, plan)`` the
    one-launch forward.  Backward rematerializes the two fp32
    pre-activations (the usual fused-epilogue remat — exactly like the
    ragged SwiGLU backward), then two planned "nt" dX products and two
    planned T2 dW products."""

    @jax.custom_vjp
    def f(x, wg, wu):
        plan = plan_fn(x, wg)
        fused = backend != "xla" and plan.fuse
        note_epilogue(family, backend == "xla" or plan.fuse)
        if fused:
            try:
                _chaos.fire("kernel_fused")
                return fused_kernel(x, wg, wu, plan)
            except _chaos.ChaosError as e:
                # Ladder rung: the one-launch SwiGLU kernel failed — fall
                # back to the two planned GEMMs + elementwise tail (whose
                # own pallas->xla rung guards the panels' kernels).
                _degraded(family, "fused->unfused", e)
        a = run_fn(x, wg, "nn", jnp.float32)
        b = run_fn(x, wu, "nn", jnp.float32)
        return (jax.nn.silu(a) * b).astype(out_dtype)

    def fwd(x, wg, wu):
        return f(x, wg, wu), (x, wg, wu)

    def bwd(res, g):
        x, wg, wu = res
        a = run_fn(x, wg, "nn", jnp.float32)
        b = run_fn(x, wu, "nn", jnp.float32)
        return _swiglu_bwd_products(run_fn, x, wg, wu, a, b, g)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _swiglu_fn(out_dtype_name: str, backend: str):
    """Custom-VJP'd dense fused SwiGLU pair (one kernel launch forward)."""
    out_dtype = jnp.dtype(out_dtype_name)
    interp = backend == "pallas_interpret"
    if backend == "xla":
        run = lambda p, q, t, dt: _REF[t](p, q, dt)  # noqa: E731
    else:
        run = lambda p, q, t, dt: _run_planned(  # noqa: E731
            p, q, t, dt, interp)

    def plan_fn(x, wg):
        plan = plan_gemm(x.shape[0], x.shape[1], wg.shape[1],
                         jnp.dtype(x.dtype).itemsize, out_dtype.itemsize,
                         epi_ops=2, swiglu=True)
        _verify("dense", (x.shape[0], x.shape[1], wg.shape[1]), plan,
                jnp.dtype(x.dtype).itemsize, out_dtype.itemsize, swiglu=True)
        note_plan_use("dense", plan)
        return plan

    def fused_kernel(x, wg, wu, plan):
        return _ops.gemm_swiglu(
            x, wg, wu, bm=plan.bm, bn=plan.bn, bk=plan.bk, edge=plan.edge,
            out_dtype=out_dtype, interpret=interp)

    return _make_swiglu_fn(out_dtype, backend, "dense", plan_fn, run,
                           fused_kernel)


def matmul_swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, *,
                  out_dtype=None, backend: str | None = None) -> jax.Array:
    """Dense fused MLP front half: silu(x @ Wg) * (x @ Wu) in ONE kernel
    launch — x streamed once against both panels, the SwiGLU nonlinearity
    applied at the fp32 accumulator flush.  ``x`` (M, K), panels (K, N)."""
    assert x.ndim == 2 and w_gate.ndim == 2, (x.shape, w_gate.shape)
    assert w_gate.shape == w_up.shape, (w_gate.shape, w_up.shape)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    backend = backend or _backend()
    if backend not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown gemm backend: {backend}")
    return _swiglu_fn(out_dtype.name, backend)(x, w_gate, w_up)


@functools.lru_cache(maxsize=None)
def _grouped_swiglu_fn(out_dtype_name: str, backend: str):
    """Custom-VJP'd grouped fused SwiGLU pair — the capacity-mode MoE
    gate/up projections (E, C, D) @ (E, D, F) as one launch.  Backward uses
    the planned batched products (dX "nt", dW the per-group T2)."""
    out_dtype = jnp.dtype(out_dtype_name)
    run = lambda p, q, t, dt: _run_planned_batched(  # noqa: E731
        p, q, t, dt, backend)

    def plan_fn(x, wg):
        plan = plan_batched_gemm(wg.shape[0], x.shape[-2], x.shape[-1],
                                 wg.shape[2], jnp.dtype(x.dtype).itemsize,
                                 out_dtype.itemsize, "none", epi_ops=2,
                                 swiglu=True)
        _verify("batched",
                (wg.shape[0], x.shape[-2], x.shape[-1], wg.shape[2]), plan,
                jnp.dtype(x.dtype).itemsize, out_dtype.itemsize, swiglu=True)
        note_plan_use("batched", plan)
        return plan

    def fused_kernel(x, wg, wu, plan):
        return _ops.batched_gemm_swiglu(
            x, wg, wu, bm=plan.bm, bn=plan.bn, bk=plan.bk, edge=plan.edge,
            out_dtype=out_dtype,
            interpret=(backend == "pallas_interpret"))

    return _make_swiglu_fn(out_dtype, backend, "batched", plan_fn, run,
                           fused_kernel)


def grouped_swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, *,
                   out_dtype=None, backend: str | None = None) -> jax.Array:
    """Grouped fused MoE front half: silu(x_g @ Wg_g) * (x_g @ Wu_g) per
    group in ONE launch — the capacity-mode analogue of ``ragged_swiglu``.
    ``x`` (G, M, K), panels (G, K, N); returns (G, M, N)."""
    assert x.ndim == 3 and w_gate.ndim == 3, (x.shape, w_gate.shape)
    assert w_gate.shape == w_up.shape, (w_gate.shape, w_up.shape)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    backend = backend or _backend()
    if backend not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown gemm backend: {backend}")
    return _grouped_swiglu_fn(out_dtype.name, backend)(x, w_gate, w_up)


# ---------------------------------------------------------------------------
# Ragged (capacity-free) grouped GEMM
# ---------------------------------------------------------------------------

def _float0_zeros(x: jax.Array):
    """Cotangent for integer primals (the group_offsets operand)."""
    import numpy as np
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _xla_ragged(x: jax.Array, w: jax.Array, offsets: jax.Array,
                trans: str, out_dtype) -> jax.Array:
    """XLA engine for the ragged product: ``jax.lax.ragged_dot`` (one pass
    over the rows)."""
    wx = w if trans == "nn" else jnp.swapaxes(w, 1, 2)
    sizes = jnp.diff(offsets).astype(jnp.int32)
    return jax.lax.ragged_dot(
        x, wx, sizes, preferred_element_type=jnp.float32).astype(out_dtype)


def _row_groups(offsets: jax.Array, t: int) -> jax.Array:
    """Owning group id per flat row: rows are sorted by group, so row r
    belongs to the group whose offset window contains it."""
    return jnp.searchsorted(offsets[1:], jnp.arange(t, dtype=offsets.dtype),
                            side="right")


def _expand_rows(v: jax.Array, offsets: jax.Array, t: int) -> jax.Array:
    """Broadcast a per-expert (G, N) flush vector to (T, N) rows — the XLA
    engine's spelling of the kernels' visit-list-indexed vector blocks."""
    return jnp.take(v, _row_groups(offsets, t), axis=0)


def _run_planned_ragged(x: jax.Array, w: jax.Array, offsets: jax.Array,
                        trans: str, out_dtype, backend: str,
                        epi: Epilogue = IDENTITY, bias=None,
                        scale=None) -> jax.Array:
    """Plan one ragged grouped GEMM off its distribution signature and run it.

    As with the batched path, the planner runs on EVERY backend (trace-time
    work; keeps the plan cache an accurate census of the irregular shapes);
    only the execution engine differs.  ``bias``/``scale`` are per-expert
    (G, N) flush vectors (the per-expert bias epilogue and the quantized
    paths' dequant), selected per tile by the visit list's group id on the
    Pallas engine and row-expanded on the XLA engine."""
    g = w.shape[0]
    k, n = (w.shape[1], w.shape[2]) if trans == "nn" else \
        (w.shape[2], w.shape[1])
    in_bytes = jnp.dtype(x.dtype).itemsize
    out_bytes = jnp.dtype(out_dtype).itemsize
    bb = _b_bytes(x, w)
    plan = plan_ragged_gemm(g, x.shape[0], k, n, in_bytes, out_bytes,
                            b_bytes=bb,
                            epilogue=None if epi.is_identity else epi)
    _verify("ragged", (g, x.shape[0], k, n), plan, in_bytes, out_bytes,
            trans=trans, epi=None if epi.is_identity else epi, b_bytes=bb)
    note_plan_use("ragged", plan)
    if not epi.is_identity:
        note_epilogue("ragged", True)
    if backend == "xla":
        return _xla_ragged_epi(x, w, offsets, trans, out_dtype, epi, bias,
                               scale)
    try:
        _chaos.fire("kernel")
        return _ops.ragged_gemm(
            x, w, offsets, bm=plan.bm, bn=plan.bn, bk=plan.bk, trans=trans,
            out_dtype=out_dtype, interpret=(backend == "pallas_interpret"),
            epilogue=None if epi.is_identity else epi, bias=bias,
            scale=scale)
    except _chaos.ChaosError as e:
        _degraded("ragged", "pallas->xla", e)
        return _xla_ragged_epi(x, w, offsets, trans, out_dtype, epi, bias,
                               scale)


def _xla_ragged_epi(x: jax.Array, w: jax.Array, offsets: jax.Array,
                    trans: str, out_dtype, epi: Epilogue, bias,
                    scale) -> jax.Array:
    """The ragged XLA engine with the per-expert flush vectors row-expanded
    — both the CPU execution path and the ragged pallas->xla ladder rung."""
    if epi.is_identity:
        return _xla_ragged(x, w, offsets, trans, out_dtype)
    z = _xla_ragged(x, w, offsets, trans, jnp.float32)
    t = x.shape[0]
    return epi.apply(
        z,
        bias=None if bias is None else _expand_rows(bias, offsets, t),
        scale=None if scale is None else _expand_rows(scale, offsets, t),
    ).astype(out_dtype)


def _run_planned_ragged_dw(x: jax.Array, dy: jax.Array, offsets: jax.Array,
                           out_dtype, backend: str) -> jax.Array:
    """The ragged T2 backward dW — planned with ragged="k" (the ragged
    dimension is the contraction; K = routed tokens >> D ~ F per group)."""
    g = offsets.shape[0] - 1
    in_bytes = jnp.dtype(x.dtype).itemsize
    out_bytes = jnp.dtype(out_dtype).itemsize
    plan = plan_ragged_gemm(g, x.shape[0], x.shape[1], dy.shape[1],
                            in_bytes, out_bytes, ragged="k")
    _verify("ragged", (g, x.shape[0], x.shape[1], dy.shape[1]), plan,
            in_bytes, out_bytes, ragged="k")
    note_plan_use("ragged", plan)
    if backend == "xla":
        # The masked per-group contraction is the XLA engine for dW.
        return _ref.ragged_matmul_dw_ref(x, dy, offsets, out_dtype=out_dtype)
    try:
        _chaos.fire("kernel")
        return _ops.ragged_gemm_dw(
            x, dy, offsets, bm=plan.bm, bn=plan.bn, bk=plan.bk,
            out_dtype=out_dtype, interpret=(backend == "pallas_interpret"))
    except _chaos.ChaosError as e:
        _degraded("ragged", "pallas->xla", e)
        return _ref.ragged_matmul_dw_ref(_wide(x), _wide(dy), offsets,
                                         out_dtype=out_dtype)


@functools.lru_cache(maxsize=None)
def _ragged_fn(out_dtype_name: str, backend: str):
    """Custom-VJP'd ragged matmul for one (dtype, backend) combo.

    Both backward GEMMs are themselves planned ragged GEMMs: dX is the "nt"
    ragged product against the same per-group panels, dW is the ragged-K T2
    grouped GEMM (``_run_planned_ragged_dw``).  group_offsets is integer
    data — its cotangent is float0."""
    out_dtype = jnp.dtype(out_dtype_name)

    @jax.custom_vjp
    def f(x, w, offsets):
        return _run_planned_ragged(x, w, offsets, "nn", out_dtype, backend)

    def fwd(x, w, offsets):
        return f(x, w, offsets), (x, w, offsets)

    def bwd(res, g):
        x, w, offsets = res
        dx = _run_planned_ragged(g, w, offsets, "nt", x.dtype, backend)
        dw = _run_planned_ragged_dw(x, g, offsets, w.dtype, backend)
        return dx, dw, _float0_zeros(offsets)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _ragged_bias_fn(out_dtype_name: str, backend: str):
    """Custom-VJP'd ragged matmul with the per-expert bias epilogue: bias is
    (G, N), its row selected per tile by the visit list's group id and added
    at the accumulator flush (RMW-safe: the masked boundary store only lands
    the visiting group's rows).  d_bias is the per-group row-sum of the
    cotangent — a segment sum over each row's owning group."""
    out_dtype = jnp.dtype(out_dtype_name)
    epi = Epilogue(bias=True)

    @jax.custom_vjp
    def f(x, w, offsets, bias):
        return _run_planned_ragged(x, w, offsets, "nn", out_dtype, backend,
                                   epi=epi, bias=bias)

    def fwd(x, w, offsets, bias):
        return f(x, w, offsets, bias), (x, w, offsets, bias)

    def bwd(res, g):
        x, w, offsets, bias = res
        dx = _run_planned_ragged(g, w, offsets, "nt", x.dtype, backend)
        dw = _run_planned_ragged_dw(x, g, offsets, w.dtype, backend)
        gid = _row_groups(offsets, g.shape[0])
        dbias = jnp.zeros((bias.shape[0], g.shape[1]), jnp.float32) \
            .at[gid].add(g.astype(jnp.float32)).astype(bias.dtype)
        return dx, dw, _float0_zeros(offsets), dbias

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _quant_ragged_fn(qcfg: "_quant.QuantConfig", out_dtype_name: str,
                     backend: str):
    """Custom-VJP'd QUANTIZED ragged matmul — int8/int4/fp8 expert panels
    with per-expert (G, N) dequant scales fused at the accumulator flush,
    so the zero-drop MoE dispatch can run int8 experts end to end.

    Forward quantizes the per-group panels per channel in-trace (frozen
    expert weights constant-fold under jit); backward is straight-through
    against the DEQUANTIZED panels: dx is the planned "nt" ragged product
    over ``dequantize(w_q)`` (bf16/fp32 backward), dw the full-precision
    ragged-K T2."""
    out_dtype = jnp.dtype(out_dtype_name)
    qepi = Epilogue(scale_vec=True)

    def quantize_w(w):
        w_q, w_scale = _quant.quantize_weights(w, qcfg)     # scale (G, N)
        if qcfg.mode == "w4":
            w_q = _quant.unpack_int4(_quant.pack_int4(w_q))
        return w_q, w_scale

    @jax.custom_vjp
    def f(x, w, offsets):
        w_q, w_scale = quantize_w(w)
        if qcfg.weight_only:
            x_run, sv = x, w_scale
        else:
            x_q, a_scale = _quant.quantize_activations(x, qcfg)
            x_run, sv = x_q, w_scale * a_scale
        return _run_planned_ragged(x_run, w_q, offsets, "nn", out_dtype,
                                   backend, epi=qepi, scale=sv)

    def fwd(x, w, offsets):
        return f(x, w, offsets), (x, w, offsets)

    def bwd(res, g):
        x, w, offsets = res
        w_q, w_scale = quantize_w(w)
        w_dq = _quant.dequantize(w_q, w_scale[:, None, :], dtype=x.dtype)
        dx = _run_planned_ragged(g, w_dq, offsets, "nt", x.dtype, backend)
        dw = _run_planned_ragged_dw(x, g, offsets, w.dtype, backend)
        return dx, dw, _float0_zeros(offsets)

    f.defvjp(fwd, bwd)
    return f


def ragged_matmul(x: jax.Array, w: jax.Array, group_offsets: jax.Array, *,
                  out_dtype=None, backend: str | None = None,
                  bias: jax.Array | None = None,
                  quant: "_quant.QuantConfig | str | None" = None
                  ) -> jax.Array:
    """Ragged grouped GEMM through the ftIMM planner; fp32 accumulation.

    ``x`` is (T, D) flat rows sorted so each group's rows are contiguous;
    ``group_offsets`` (G+1,) prefix sums with offsets[0] == 0 and
    offsets[G] == T (every row owned — capacity-free, nothing dropped);
    ``w`` is (G, D, F) per-group panels.  Returns (T, F).  The capacity-free
    MoE expert projections route here instead of the padded grouped path.

    ``bias`` (G, F) adds a per-expert bias at the accumulator flush (fully
    differentiable — d_bias segment-sums the cotangent per expert).
    ``quant`` quantizes the expert panels in-trace (per-expert per-channel
    scales) and runs the narrow-dtype kernel with the dequant fused at the
    flush; straight-through backward against the dequantized panels."""
    assert x.ndim == 2 and w.ndim == 3, (x.shape, w.shape)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    backend = backend or _backend()
    if backend not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown gemm backend: {backend}")
    qcfg = _quant.resolve(quant)
    if not qcfg.is_noop:
        if bias is not None:
            raise ValueError("quantized ragged matmul does not take a bias "
                             "operand; apply it as a separate epilogue")
        return _quant_ragged_fn(qcfg, out_dtype.name,
                                backend)(x, w, group_offsets)
    if bias is not None:
        _check_vectors("ragged", (w.shape[0], x.shape[0], w.shape[1],
                                  w.shape[2]), Epilogue(bias=True), bias,
                       None)
        return _ragged_bias_fn(out_dtype.name,
                               backend)(x, w, group_offsets, bias)
    return _ragged_fn(out_dtype.name, backend)(x, w, group_offsets)


@functools.lru_cache(maxsize=None)
def _ragged_swiglu_fn(out_dtype_name: str, backend: str):
    """Custom-VJP'd fused ragged SwiGLU pair (one kernel launch forward).

    Backward rematerializes the two fp32 pre-activations with planned ragged
    GEMMs (the usual fused-epilogue remat), then runs two planned "nt" dX
    products and two planned ragged-K dW products."""
    out_dtype = jnp.dtype(out_dtype_name)

    def _plan(x, wg):
        in_bytes = jnp.dtype(x.dtype).itemsize
        plan = plan_ragged_gemm(wg.shape[0], x.shape[0], wg.shape[1],
                                wg.shape[2], in_bytes, out_dtype.itemsize,
                                swiglu=True)
        _verify("ragged", (wg.shape[0], x.shape[0], wg.shape[1],
                           wg.shape[2]), plan, in_bytes, out_dtype.itemsize,
                swiglu=True)
        note_plan_use("ragged", plan)
        return plan

    @jax.custom_vjp
    def f(x, wg, wu, offsets):
        plan = _plan(x, wg)
        if backend == "xla":
            a = _xla_ragged(x, wg, offsets, "nn", jnp.float32)
            b = _xla_ragged(x, wu, offsets, "nn", jnp.float32)
            return (jax.nn.silu(a) * b).astype(out_dtype)
        try:
            _chaos.fire("kernel_fused")
            return _ops.ragged_gemm_swiglu(
                x, wg, wu, offsets, bm=plan.bm, bn=plan.bn, bk=plan.bk,
                out_dtype=out_dtype,
                interpret=(backend == "pallas_interpret"))
        except _chaos.ChaosError as e:
            _degraded("ragged", "fused->unfused", e)
        a = _run_planned_ragged(x, wg, offsets, "nn", jnp.float32, backend)
        b = _run_planned_ragged(x, wu, offsets, "nn", jnp.float32, backend)
        return (jax.nn.silu(a) * b).astype(out_dtype)

    def fwd(x, wg, wu, offsets):
        return f(x, wg, wu, offsets), (x, wg, wu, offsets)

    def bwd(res, g):
        x, wg, wu, offsets = res
        a = _run_planned_ragged(x, wg, offsets, "nn", jnp.float32, backend)
        b = _run_planned_ragged(x, wu, offsets, "nn", jnp.float32, backend)
        sg = jax.nn.sigmoid(a)
        g32 = g.astype(jnp.float32)
        da = (g32 * b * sg * (1.0 + a * (1.0 - sg))).astype(x.dtype)
        db = (g32 * a * sg).astype(x.dtype)
        dx = (_run_planned_ragged(da, wg, offsets, "nt", jnp.float32, backend)
              + _run_planned_ragged(db, wu, offsets, "nt", jnp.float32,
                                    backend)).astype(x.dtype)
        dwg = _run_planned_ragged_dw(x, da, offsets, wg.dtype, backend)
        dwu = _run_planned_ragged_dw(x, db, offsets, wu.dtype, backend)
        return dx, dwg, dwu, _float0_zeros(offsets)

    f.defvjp(fwd, bwd)
    return f


def ragged_swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                  group_offsets: jax.Array, *, out_dtype=None,
                  backend: str | None = None) -> jax.Array:
    """Fused ragged MoE MLP front half: silu(x @ Wg_g) * (x @ Wu_g) per group
    in ONE kernel launch (same contract as ``ragged_matmul``)."""
    assert x.ndim == 2 and w_gate.ndim == 3, (x.shape, w_gate.shape)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    backend = backend or _backend()
    if backend not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown gemm backend: {backend}")
    return _ragged_swiglu_fn(out_dtype.name, backend)(
        x, w_gate, w_up, group_offsets)


def clear_dispatch_caches() -> None:
    """Drop the custom-VJP'd dispatch function caches so the next call
    re-traces against the current planner state (part of the single
    ``tuner.clear_plan_cache`` reset: the cached closures re-consult the
    planners at trace time, and stale jit entries keyed on old blocks are
    unreachable once the planners re-decide)."""
    _pallas_fn.cache_clear()
    _quant_fn.cache_clear()
    _batched_fn.cache_clear()
    _batched_bias_fn.cache_clear()
    _ragged_fn.cache_clear()
    _ragged_bias_fn.cache_clear()
    _quant_ragged_fn.cache_clear()
    _ragged_swiglu_fn.cache_clear()
    _swiglu_fn.cache_clear()
    _grouped_swiglu_fn.cache_clear()
    _verify_cached.cache_clear()
    # The warn-once set must reset with everything else: after a full
    # cache reset a recurring degradation should log again instead of
    # being silently swallowed by a stale dedup key.
    _WARNED_RUNGS.clear()


def project(x: jax.Array, w: jax.Array, *, out_dtype=None,
            backend: str | None = None,
            epilogue: Epilogue | None = None,
            bias: jax.Array | None = None,
            residual: jax.Array | None = None,
            quant: "_quant.QuantConfig | str | None" = None) -> jax.Array:
    """(..., D) @ (D, N) -> (..., N): flattens leading dims into the paper's
    M dimension (tokens — typically the tall axis of T1/T3).  ``epilogue``
    fuses the layer's elementwise tail into the projection; ``residual``
    (..., N) is flattened alongside x, ``bias`` is (N,).  ``quant`` routes
    through the managed quantized engine (see ``matmul``)."""
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= s
    res = None if residual is None else residual.reshape(m, w.shape[-1])
    y = matmul(x.reshape(m, x.shape[-1]), w, out_dtype=out_dtype,
               backend=backend, epilogue=epilogue, bias=bias, residual=res,
               quant=quant)
    return y.reshape(*lead, w.shape[-1])


def project_swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, *,
                   out_dtype=None, backend: str | None = None) -> jax.Array:
    """(..., D) fused SwiGLU front half: silu(x @ Wg) * (x @ Wu) with the
    leading dims flattened into M — ONE kernel launch for a dense MLP's
    gate/up pair."""
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= s
    y = matmul_swiglu(x.reshape(m, x.shape[-1]), w_gate, w_up,
                      out_dtype=out_dtype, backend=backend)
    return y.reshape(*lead, w_gate.shape[-1])
