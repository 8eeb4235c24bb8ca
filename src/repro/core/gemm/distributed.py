"""Mesh-scale ftIMM executors: one ``shard_map`` engine per plan family.

The tuner (``tuner.plan_*``) decides *placement jointly with blocking* — a
``Plan`` whose optional ``Placement`` names the cross-chip strategy, its
modeled ICI term, and (new) the overlap ``schedule``.  This module is the
execution side of that hierarchy:

  * **dense** — ``dist_matmul``: the paper's two multi-core strategies.
    Alg. 4 (m_parallel) shards A's M rows over the axis with B replicated
    (no steady-state collective); Alg. 5 (k_parallel) shards the contraction
    and reduces the fp32 partials over ICI — either as one ``psum`` after
    the local GEMM ("gather" schedule) or as the overlapped ring collective
    matmul ("ring" schedule): output columns are chunked over shard-steps
    and each hop's partial-sum transfer overlaps the next chunk's compute,
    the mesh-level analogue of the paper's core-level DMA pipelining.

  * **batched/grouped** — ``dist_batched_matmul``: the batch/expert dim
    shards over the axis (expert_parallel for the capacity-mode grouped MoE
    GEMMs), shared 2-D operands replicate, per-entry M/K/N stay local.

  * **ragged** — ``ep_ragged_matmul`` / ``ep_ragged_swiglu`` /
    ``ep_ragged_moe`` (the fused pipeline the MoE layer actually routes
    through — one d_model-wide exchange each way, the d_ff hidden never
    crosses the axis): expert-parallel capacity-free MoE.  Rows arrive
    sorted by group with ``group_offsets`` prefix sums, and experts are
    contiguously owned by shards, so shard s's tokens are the *contiguous
    window* [offsets[s*G_l], offsets[(s+1)*G_l]) of the global row array.
    Two schedules realize the exchange+GEMM (``core.gemm.collective``):

      - "gather": the token exchange runs first (the true ragged
        all-to-all when ``jax.lax.ragged_all_to_all`` is available and
        passes the mesh probe, otherwise the dense all_gather/psum_scatter
        realization), then ONE per-shard ragged GEMM over the worst-case
        window.  Empty shards skip the window slice + GEMM entirely
        (``lax.cond`` short-circuit); the collectives still run on every
        shard, as they must.
      - "ring": token blocks rotate around the axis via ``ppermute`` and
        each shard computes only the blocks intersecting its owned window —
        per-shard compute scales with the rows the shard actually owns
        instead of T, and the block transfers hide behind compute.

    The per-shard GEMM is the already-planned ragged kernel, and the custom
    VJP reuses the per-shard ragged dX ("nt") and dW (ragged-K T2) products
    with the inverse exchange — gradients for an expert's panel never leave
    the shard that owns it.  The backward's (cotangent, activation) pair
    crosses the axis as ONE fused exchange (concatenated columns), not two.

Strategy and schedule selection use the same CMR-with-collective-term
scoring as the paper's dynamic adjusting (``tuner.plan_gemm(...,
num_shards=n)`` / ``tuner.preferred_ep_schedule``); ``REPRO_EP_SCHEDULE``
forces the EP schedule for experiments.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ...kernels.ftimm.epilogue import IDENTITY, Epilogue
from ...runtime import chaos as _chaos
from ..compat import shard_map_unchecked as shard_map
from . import collective
from .dispatch import (_backend, _check_epi, _degraded, _float0_zeros,
                       _run_planned_ragged, _run_planned_ragged_dw,
                       batched_matmul, matmul, ragged_matmul, ragged_swiglu)
from .tuner import note_plan_use, plan_distributed, preferred_ep_schedule

ENV_EP_SCHEDULE = "REPRO_EP_SCHEDULE"


def _axes(axis) -> tuple[str, ...]:
    return tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)


def _axis_size(mesh: Mesh, axis) -> int:
    return int(math.prod(mesh.shape[a] for a in _axes(axis)))


def _spec_entry(axis):
    ax = _axes(axis)
    return ax if len(ax) > 1 else ax[0]


def choose_strategy(m: int, k: int, n: int, num_cores: int,
                    in_bytes: int = 4) -> str:
    # The compat planner handles num_cores == 1 (a size-1 mesh axis) too.
    return plan_distributed(m, k, n, num_cores, in_bytes).strategy


def dist_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "model",
    strategy: str | None = None,
    schedule: str | None = None,
    out_dtype=None,
    backend: str | None = None,
    epilogue: Epilogue | None = None,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
) -> jax.Array:
    """C = A(M,K) @ B(K,N) parallelized over ``mesh[axis]``.

    Operands may be global arrays with any sharding; shard_map re-shards to
    the strategy's layout.  Output is M-sharded (m_parallel) or replicated
    (k_parallel) over ``axis``.

    ``schedule`` picks the k_parallel reduction realization: "gather" is
    compute-then-psum; "ring" is the overlapped collective matmul (chunked
    output columns rotating partial sums, transfer hidden behind compute).
    ``None`` defers to the plan (m_parallel is always "gather" — it has no
    steady-state collective to overlap).

    ``epilogue`` (with ``bias`` (N,) / ``residual`` (M, N)) fuses the
    elementwise tail per shard: under m_parallel the residual's rows shard
    with A and each shard flushes its own fused tile; under k_parallel the
    tail applies AFTER the full reduction of the fp32 partials (the
    activation is nonlinear — applying it per shard would be wrong), still
    inside the shard_map body, so no extra pass over a stored output either
    way.
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(
            f"dist_matmul contraction mismatch: a has shape {a.shape} "
            f"(K = {k}) but b has shape {b.shape} (K = {k2})")
    epi = IDENTITY if epilogue is None else epilogue
    _check_epi(epi, bias, residual)
    nc = mesh.shape[axis]
    if strategy is None:
        plan = plan_distributed(m, k, n, nc, jnp.dtype(a.dtype).itemsize)
        note_plan_use("dist_dense", plan)
        strategy = plan.strategy
        if schedule is None:
            schedule = plan.placement.schedule
    schedule = schedule or "gather"
    if schedule not in collective.SCHEDULES:
        raise ValueError(f"unknown schedule: {schedule!r}")
    if schedule == "ring" and strategy != "k_parallel":
        raise ValueError(
            f"ring schedule is undefined for {strategy} (no steady-state "
            "collective to overlap)")
    out_dtype = jnp.dtype(out_dtype or a.dtype)

    bias2 = None if bias is None else bias.reshape(1, n)

    if strategy == "m_parallel":
        pad_m = (-m) % nc
        a_p = jnp.pad(a, ((0, pad_m), (0, 0))) if pad_m else a
        res_p = None
        if residual is not None:
            res_p = jnp.pad(residual, ((0, pad_m), (0, 0))) if pad_m \
                else residual

        in_specs = [P(axis, None), P(None, None)]
        operands = [a_p, b]
        if bias2 is not None:
            in_specs.append(P(None, None))
            operands.append(bias2)
        if res_p is not None:
            in_specs.append(P(axis, None))      # residual rows shard with A
            operands.append(res_p)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=P(axis, None),
        )
        def f(a_l, b_l, *extras_l):
            bias_l, res_l, scale_l = epi.unpack(extras_l)
            bias_l = None if bias_l is None else bias_l.reshape(-1)
            scale_l = None if scale_l is None else scale_l.reshape(-1)
            return matmul(a_l, b_l, out_dtype=out_dtype, backend=backend,
                          epilogue=epilogue, bias=bias_l, residual=res_l,
                          scale=scale_l)

        out = f(*operands)
        return out[:m] if pad_m else out

    if strategy == "k_parallel":
        pad_k = (-k) % nc
        # The ring schedule chunks the output columns over shard-steps.
        pad_n = (-n) % nc if schedule == "ring" else 0
        a_p = jnp.pad(a, ((0, 0), (0, pad_k))) if pad_k else a
        b_p = jnp.pad(b, ((0, pad_k), (0, pad_n))) if (pad_k or pad_n) else b
        bias_p = bias2
        if bias2 is not None and pad_n:
            bias_p = jnp.pad(bias2, ((0, 0), (0, pad_n)))
        res_p = residual
        if residual is not None and pad_n:
            res_p = jnp.pad(residual, ((0, 0), (0, pad_n)))

        in_specs = [P(None, axis), P(axis, None)]
        operands = [a_p, b_p]
        if bias_p is not None:
            in_specs.append(P(None, None))
            operands.append(bias_p)
        if res_p is not None:
            in_specs.append(P(None, None))
            operands.append(res_p)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=P(None, None),
        )
        def f(a_l, b_l, *extras_l):
            if schedule == "ring":
                full = collective.ring_kparallel(
                    a_l, b_l, axis, nc,
                    lambda al, bc: matmul(al, bc, out_dtype=jnp.float32,
                                          backend=backend))
            else:
                partial_c = matmul(a_l, b_l, out_dtype=jnp.float32,
                                   backend=backend)
                # Paper Alg. 5 line 12: reduce partial C among cores.
                full = jax.lax.psum(partial_c, axis)
            if epi.is_identity:
                return full
            bias_l, res_l, scale_l = epi.unpack(extras_l)
            bias_l = None if bias_l is None else bias_l.reshape(-1)
            scale_l = None if scale_l is None else scale_l.reshape(-1)
            return epi.apply(full, bias=bias_l, residual=res_l,
                             scale=scale_l)

        out = f(*operands).astype(out_dtype)
        return out[:, :n] if pad_n else out

    raise ValueError(f"unknown strategy: {strategy}")


def dist_batched_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    axis="data",
    trans: str = "nn",
    out_dtype=None,
    backend: str | None = None,
) -> jax.Array:
    """Batched/grouped GEMM with the batch (expert) dim sharded over
    ``mesh[axis]`` — the expert_parallel placement of the capacity-mode MoE
    GEMMs (E, C, D) @ (E, D, F).  A 2-D (shared) operand replicates; the
    per-entry GEMM runs through the planned ``batched_matmul`` locally."""
    if a.ndim != 3 and b.ndim != 3:
        raise ValueError(f"need a batched operand: {a.shape} / {b.shape}")
    g = a.shape[0] if a.ndim == 3 else b.shape[0]
    nc = _axis_size(mesh, axis)
    pad_g = (-g) % nc
    ax = _spec_entry(axis)

    def pad3(x):
        if x.ndim != 3 or not pad_g:
            return x
        return jnp.pad(x, ((0, pad_g), (0, 0), (0, 0)))

    a_p, b_p = pad3(a), pad3(b)
    spec3 = P(ax, None, None)
    spec2 = P(None, None)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec3 if a.ndim == 3 else spec2,
                  spec3 if b.ndim == 3 else spec2),
        out_specs=spec3,
    )
    def f(a_l, b_l):
        return batched_matmul(a_l, b_l, trans=trans, out_dtype=out_dtype,
                              backend=backend)

    out = f(a_p, b_p)
    return out[:g] if pad_g else out


# ---------------------------------------------------------------------------
# Expert-parallel ragged (capacity-free) grouped GEMM
# ---------------------------------------------------------------------------

def _sidx(axis) -> jax.Array:
    """Linear shard index along (possibly multiple) mesh axes, major-first —
    matching the row-major layout of ``P((a, b), ...)``."""
    idx = jnp.int32(0)
    for a in _axes(axis):
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


_mask_rows = collective.mask_rows


def _resolve_ep_schedule(schedule: str | None, axes: tuple, nc: int,
                         g: int, total: int, k: int, n: int,
                         in_bytes: int, out_bytes: int) -> str:
    """Explicit kwarg > ``REPRO_EP_SCHEDULE`` > the planner's preference.
    The ring rotates ONE named axis (``ppermute``), so multi-axis EP and
    degenerate single-shard meshes fall back to the gather schedule.

    The planner preference is environment-aware: on the CPU backend the
    mesh is fake host devices timesharing one core, so the shards' local
    GEMMs serialize and the preference is evaluated with the local term
    scaled by ``nc`` (``serial=nc``) — on a real accelerator mesh each
    shard has its own chip and ``serial=1``."""
    if schedule is None:
        schedule = os.environ.get(ENV_EP_SCHEDULE) or None
    if schedule is None:
        serial = nc if jax.default_backend() == "cpu" else 1
        schedule = preferred_ep_schedule(g, total, k, n, in_bytes,
                                         out_bytes, nc, serial=serial)
    if schedule not in collective.SCHEDULES:
        raise ValueError(f"unknown EP schedule: {schedule!r}")
    if schedule == "ring" and (len(axes) > 1 or nc <= 1):
        schedule = "gather"
    return schedule


def _gather_exchange_fwd(x_l, offs, g_l, axis, ax, nc, method, compute,
                         out_width, out_dtype):
    """Gather-schedule forward: exchange COLLECTIVES run unconditionally on
    every shard; the window slice + GEMM are ``lax.cond``-skipped when the
    shard owns zero rows (the empty-shard short-circuit)."""
    tl = x_l.shape[0]
    t = nc * tl
    payload, loffs_abs, start, stop = collective.dispatch_payload(
        x_l, offs, g_l, axis, ax, nc, method, _sidx(axis))
    wlen = stop - start

    def run():
        win = collective.window_from_payload(payload, start, method)
        loffs = (loffs_abs - start).astype(jnp.int32)
        return _mask_rows(compute(win, loffs, wlen), wlen)

    y_win = jax.lax.cond(wlen > 0, run,
                         lambda: jnp.zeros((t, out_width), out_dtype))
    return collective.combine_rows(y_win, offs, g_l, axis, ax, nc, method,
                                   start, tl)


def _gather_exchange_bwd(ct_l, x_l, offs, g_l, axis, ax, nc, method,
                         compute, dw_zeros):
    """Gather-schedule backward with the FUSED exchange: the cotangent and
    activation cross the axis as one concatenated payload (one collective
    latency, not two), then split back in the shard's window.  ``compute``
    maps (ct_win, x_win, loffs, wlen) -> (dx_win, (dw, ...))."""
    tl = x_l.shape[0]
    t = nc * tl
    n_ct = ct_l.shape[1]
    cat_dt = jnp.promote_types(ct_l.dtype, x_l.dtype)
    cat = jnp.concatenate([ct_l.astype(cat_dt), x_l.astype(cat_dt)], axis=1)
    payload, loffs_abs, start, stop = collective.dispatch_payload(
        cat, offs, g_l, axis, ax, nc, method, _sidx(axis))
    wlen = stop - start

    def run():
        win = _mask_rows(collective.window_from_payload(payload, start,
                                                        method), wlen)
        ct_win = win[:, :n_ct].astype(ct_l.dtype)
        x_win = win[:, n_ct:].astype(x_l.dtype)
        loffs = (loffs_abs - start).astype(jnp.int32)
        dx_win, dw_c = compute(ct_win, x_win, loffs, wlen)
        return (_mask_rows(dx_win, wlen),) + tuple(dw_c)

    zero = ((jnp.zeros((t, x_l.shape[1]), x_l.dtype),)
            + tuple(jnp.zeros_like(z) for z in dw_zeros))
    out = jax.lax.cond(wlen > 0, run, lambda: zero)
    dx_l = collective.combine_rows(out[0], offs, g_l, axis, ax, nc, method,
                                   start, tl)
    return dx_l, out[1:]


@functools.lru_cache(maxsize=32)   # keyed on the Mesh: bound it
def _ep_ragged_fn(mesh: Mesh, axis: tuple, out_dtype_name: str, backend: str,
                  schedule: str = "gather", method: str = "dense"):
    """Custom-VJP'd expert-parallel ragged matmul for one (mesh, axis,
    dtype, backend, schedule, exchange-method) combo.  The VJP reuses the
    planned per-shard ragged products: dX is the "nt" product against the
    shard's own panels (then the inverse exchange), dW is the ragged-K T2
    product of the shard's token window — expert gradients never cross the
    axis."""
    out_dtype = jnp.dtype(out_dtype_name)
    ax = _spec_entry(axis)
    rows, experts, rep = P(ax, None), P(ax, None, None), P(None)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(rows, experts, rep), out_specs=rows)
    def fwd_local(x_l, w_l, offs):
        g_l, n = w_l.shape[0], w_l.shape[2]

        def compute(win, loffs, wlen):
            return ragged_matmul(win, w_l, loffs, out_dtype=out_dtype,
                                 backend=backend)

        if schedule == "ring":
            nc = _axis_size(mesh, axis)
            return collective.ring_forward(x_l, offs, g_l, axis[0], nc,
                                           compute, n, out_dtype)
        nc = _axis_size(mesh, axis)
        return _gather_exchange_fwd(x_l, offs, g_l, axis, ax, nc, method,
                                    compute, n, out_dtype)

    @jax.custom_vjp
    def f(x, w, offsets):
        return fwd_local(x, w, offsets)

    def fwd(x, w, offsets):
        return f(x, w, offsets), (x, w, offsets)

    def bwd(res, ct):
        x, w, offsets = res

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(rows, rows, experts, rep),
                           out_specs=(rows, experts))
        def bwd_local(ct_l, x_l, w_l, offs):
            g_l = w_l.shape[0]
            nc = _axis_size(mesh, axis)

            def compute(ct_win, x_win, loffs, wlen):
                ct_win = _mask_rows(ct_win, wlen)
                x_win = _mask_rows(x_win, wlen)
                dx_win = _run_planned_ragged(ct_win, w_l, loffs, "nt",
                                             x_l.dtype, backend)
                dw_c = _run_planned_ragged_dw(x_win, ct_win, loffs,
                                              w_l.dtype, backend)
                return dx_win, (dw_c,)

            if schedule == "ring":
                dx_l, dws = collective.ring_backward(
                    ct_l, x_l, offs, g_l, axis[0], nc, compute,
                    (jnp.zeros(w_l.shape, w_l.dtype),))
            else:
                dx_l, dws = _gather_exchange_bwd(
                    ct_l, x_l, offs, g_l, axis, ax, nc, method, compute,
                    (jnp.zeros(w_l.shape, w_l.dtype),))
            return dx_l, dws[0]

        dx, dw = bwd_local(ct, x, w, offsets)
        return dx, dw, _float0_zeros(offsets)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=32)   # keyed on the Mesh: bound it
def _ep_ragged_swiglu_fn(mesh: Mesh, axis: tuple, out_dtype_name: str,
                         backend: str, schedule: str = "gather",
                         method: str = "dense"):
    """Expert-parallel fused ragged SwiGLU: one exchange in, the fused
    silu(gate)*up pair per shard, one exchange back.  Backward follows the
    single-device fused-epilogue recipe (rematerialize the two fp32
    pre-activations per shard, two "nt" dX products + two ragged-K dW
    products), all inside the shard's token window."""
    out_dtype = jnp.dtype(out_dtype_name)
    ax = _spec_entry(axis)
    rows, experts, rep = P(ax, None), P(ax, None, None), P(None)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(rows, experts, experts, rep),
                       out_specs=rows)
    def fwd_local(x_l, wg_l, wu_l, offs):
        g_l, n = wg_l.shape[0], wg_l.shape[2]
        nc = _axis_size(mesh, axis)

        def compute(win, loffs, wlen):
            return ragged_swiglu(win, wg_l, wu_l, loffs, out_dtype=out_dtype,
                                 backend=backend)

        if schedule == "ring":
            return collective.ring_forward(x_l, offs, g_l, axis[0], nc,
                                           compute, n, out_dtype)
        return _gather_exchange_fwd(x_l, offs, g_l, axis, ax, nc, method,
                                    compute, n, out_dtype)

    @jax.custom_vjp
    def f(x, wg, wu, offsets):
        return fwd_local(x, wg, wu, offsets)

    def fwd(x, wg, wu, offsets):
        return f(x, wg, wu, offsets), (x, wg, wu, offsets)

    def bwd(res, ct):
        x, wg, wu, offsets = res

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(rows, rows, experts, experts, rep),
                           out_specs=(rows, experts, experts))
        def bwd_local(ct_l, x_l, wg_l, wu_l, offs):
            g_l = wg_l.shape[0]
            nc = _axis_size(mesh, axis)

            def compute(ct_win, x_win, loffs, wlen):
                ct_win = _mask_rows(ct_win, wlen)
                x_win = _mask_rows(x_win, wlen)
                a = _run_planned_ragged(x_win, wg_l, loffs, "nn",
                                        jnp.float32, backend)
                b = _run_planned_ragged(x_win, wu_l, loffs, "nn",
                                        jnp.float32, backend)
                sg = jax.nn.sigmoid(a)
                ct32 = ct_win.astype(jnp.float32)
                da = (ct32 * b * sg
                      * (1.0 + a * (1.0 - sg))).astype(x_l.dtype)
                db = (ct32 * a * sg).astype(x_l.dtype)
                dx_win = (
                    _run_planned_ragged(da, wg_l, loffs, "nt", jnp.float32,
                                        backend)
                    + _run_planned_ragged(db, wu_l, loffs, "nt", jnp.float32,
                                          backend)).astype(x_l.dtype)
                dwg_c = _run_planned_ragged_dw(x_win, da, loffs, wg_l.dtype,
                                               backend)
                dwu_c = _run_planned_ragged_dw(x_win, db, loffs, wu_l.dtype,
                                               backend)
                return dx_win, (dwg_c, dwu_c)

            dw_zeros = (jnp.zeros(wg_l.shape, wg_l.dtype),
                        jnp.zeros(wu_l.shape, wu_l.dtype))
            if schedule == "ring":
                dx_l, dws = collective.ring_backward(
                    ct_l, x_l, offs, g_l, axis[0], nc, compute, dw_zeros)
            else:
                dx_l, dws = _gather_exchange_bwd(
                    ct_l, x_l, offs, g_l, axis, ax, nc, method, compute,
                    dw_zeros)
            return dx_l, dws[0], dws[1]

        dx, dwg, dwu = bwd_local(ct, x, wg, wu, offsets)
        return dx, dwg, dwu, _float0_zeros(offsets)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=32)   # keyed on the Mesh: bound it
def _ep_ragged_moe_fn(mesh: Mesh, axis: tuple, out_dtype_name: str,
                      backend: str, schedule: str = "gather",
                      method: str = "dense"):
    """Fused expert-parallel ragged MoE MLP: ONE token exchange each way for
    the whole silu(x Wg)*(x Wu) Wd pipeline.  The (rows, d_ff) hidden is
    produced and consumed on the shard that owns the expert — running
    ``ep_ragged_swiglu`` then ``ep_ragged_matmul`` instead would exchange it
    back and immediately re-gather it into the exact same windows.
    Backward: ONE fused (cotangent, x) exchange in, all three dW products
    and both dX products per shard, one inverse exchange for dX."""
    out_dtype = jnp.dtype(out_dtype_name)
    ax = _spec_entry(axis)
    rows, experts, rep = P(ax, None), P(ax, None, None), P(None)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(rows, experts, experts, experts, rep),
                       out_specs=rows)
    def fwd_local(x_l, wg_l, wu_l, wd_l, offs):
        g_l, n = wg_l.shape[0], wd_l.shape[2]
        nc = _axis_size(mesh, axis)

        def compute(win, loffs, wlen):
            h_win = ragged_swiglu(win, wg_l, wu_l, loffs,
                                  out_dtype=out_dtype, backend=backend)
            return ragged_matmul(_mask_rows(h_win, wlen), wd_l, loffs,
                                 out_dtype=out_dtype, backend=backend)

        if schedule == "ring":
            return collective.ring_forward(x_l, offs, g_l, axis[0], nc,
                                           compute, n, out_dtype)
        return _gather_exchange_fwd(x_l, offs, g_l, axis, ax, nc, method,
                                    compute, n, out_dtype)

    @jax.custom_vjp
    def f(x, wg, wu, wd, offsets):
        return fwd_local(x, wg, wu, wd, offsets)

    def fwd(x, wg, wu, wd, offsets):
        return f(x, wg, wu, wd, offsets), (x, wg, wu, wd, offsets)

    def bwd(res, ct):
        x, wg, wu, wd, offsets = res

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(rows, rows, experts, experts, experts, rep),
            out_specs=(rows, experts, experts, experts))
        def bwd_local(ct_l, x_l, wg_l, wu_l, wd_l, offs):
            g_l = wg_l.shape[0]
            nc = _axis_size(mesh, axis)

            def compute(ct_win, x_win, loffs, wlen):
                ct_win = _mask_rows(ct_win, wlen)
                x_win = _mask_rows(x_win, wlen)
                # Rematerialize the fp32 pre-activations and the hidden.
                a = _run_planned_ragged(x_win, wg_l, loffs, "nn",
                                        jnp.float32, backend)
                b = _run_planned_ragged(x_win, wu_l, loffs, "nn",
                                        jnp.float32, backend)
                sg = jax.nn.sigmoid(a)
                h_win = _mask_rows((a * sg * b).astype(x_l.dtype), wlen)
                # Down projection: dH and dWd stay on the owning shard.
                dh = _mask_rows(
                    _run_planned_ragged(ct_win, wd_l, loffs, "nt",
                                        jnp.float32, backend), wlen)
                dwd_c = _run_planned_ragged_dw(h_win, ct_win, loffs,
                                               wd_l.dtype, backend)
                # SwiGLU epilogue backward, then the two dX products.
                da = (dh * b * sg * (1.0 + a * (1.0 - sg))).astype(x_l.dtype)
                db = (dh * a * sg).astype(x_l.dtype)
                dx_win = (
                    _run_planned_ragged(da, wg_l, loffs, "nt", jnp.float32,
                                        backend)
                    + _run_planned_ragged(db, wu_l, loffs, "nt", jnp.float32,
                                          backend)).astype(x_l.dtype)
                dwg_c = _run_planned_ragged_dw(x_win, da, loffs, wg_l.dtype,
                                               backend)
                dwu_c = _run_planned_ragged_dw(x_win, db, loffs, wu_l.dtype,
                                               backend)
                return dx_win, (dwg_c, dwu_c, dwd_c)

            dw_zeros = (jnp.zeros(wg_l.shape, wg_l.dtype),
                        jnp.zeros(wu_l.shape, wu_l.dtype),
                        jnp.zeros(wd_l.shape, wd_l.dtype))
            if schedule == "ring":
                dx_l, dws = collective.ring_backward(
                    ct_l, x_l, offs, g_l, axis[0], nc, compute, dw_zeros)
            else:
                dx_l, dws = _gather_exchange_bwd(
                    ct_l, x_l, offs, g_l, axis, ax, nc, method, compute,
                    dw_zeros)
            return dx_l, dws[0], dws[1], dws[2]

        dx, dwg, dwu, dwd = bwd_local(ct, x, wg, wu, wd, offsets)
        return dx, dwg, dwu, dwd, _float0_zeros(offsets)

    f.defvjp(fwd, bwd)
    return f


def clear_executor_caches() -> None:
    """Drop the bounded mesh-keyed executor caches.  Part of the single
    ``tuner.clear_plan_cache`` reset: these closures re-plan their ragged
    GEMMs at trace time, so an executor traced before a spec change /
    plan-cache load would keep serving the stale blocking forever (the bug:
    ``clear_plan_cache`` used to clear only the five planner LRUs)."""
    _ep_ragged_fn.cache_clear()
    _ep_ragged_swiglu_fn.cache_clear()
    _ep_ragged_moe_fn.cache_clear()


def _ep_prepare(x: jax.Array, w: jax.Array, mesh: Mesh, axis):
    if x.ndim != 2 or w.ndim != 3:
        raise ValueError((x.shape, w.shape))
    g = w.shape[0]
    nc = _axis_size(mesh, axis)
    if g % nc:
        raise ValueError(
            f"expert count {g} not divisible by mesh axis {axis} ({nc})")
    t = x.shape[0]
    pad_t = (-t) % nc
    x_p = jnp.pad(x, ((0, pad_t), (0, 0))) if pad_t else x
    return x_p, t, pad_t


def _ep_executor_args(x_p, w, out_dtype, mesh, axis, schedule):
    """Resolve the (schedule, exchange-method) pair for one EP call: the
    planner's preferred schedule for this shape unless forced, and the
    probed exchange realization for this mesh.  Both land in the executor's
    cache key so env/plan changes retrace instead of serving stale."""
    axes = _axes(axis)
    nc = _axis_size(mesh, axis)
    g, k, n = w.shape[0], w.shape[1], w.shape[2]
    schedule = _resolve_ep_schedule(
        schedule, axes, nc, g, x_p.shape[0], k, n,
        jnp.dtype(x_p.dtype).itemsize, out_dtype.itemsize)
    method = collective.exchange_method(mesh, axes)
    return axes, schedule, method


def _ep_ladder(run, schedule: str, single):
    """The EP fallback ladder: ring -> gather -> single-device, taken only
    on an injected ``chaos.ChaosError`` — a real executor or collective
    error propagates.

    ``run(schedule)`` builds + calls the sharded executor; ``single()`` is
    the last rung — the plain planned ragged op on the GLOBAL arrays, which
    is numerically the same computation with the exchange gone (under jit
    GSPMD gathers sharded operands implicitly).  Each degradation is
    counted in ``plan_mode_stats()['degraded']`` and logged once.  The
    ``ep_ring``/``ep_gather`` chaos sites arm here, at trace time, so a
    jitted program replays its injected degradations deterministically."""
    if schedule == "ring":
        try:
            _chaos.fire("ep_ring")
            return run("ring")
        except _chaos.ChaosError as e:
            _degraded("ep", "ring->gather", e)
            schedule = "gather"
    try:
        _chaos.fire("ep_gather")
        return run(schedule)
    except _chaos.ChaosError as e:
        _degraded("ep", "gather->single", e)
        return single()


def ep_ragged_matmul(x: jax.Array, w: jax.Array, group_offsets: jax.Array, *,
                     mesh: Mesh, axis="data", out_dtype=None,
                     backend: str | None = None,
                     schedule: str | None = None) -> jax.Array:
    """Expert-parallel ragged grouped GEMM over ``mesh[axis]``.

    Same contract as ``ragged_matmul`` — ``x`` (T, D) rows sorted so each
    group's rows are contiguous, ``group_offsets`` (G+1,) prefix sums,
    ``w`` (G, D, F) per-group panels, G divisible by the axis size — but the
    expert dim is sharded: tokens travel to the shard owning their expert
    (the contiguous-window exchange keyed by the prefix sums), the planned
    per-shard ragged kernel runs on G/num_shards local panels, and the
    inverse exchange restores the global row order.  ``schedule`` picks
    "ring" (overlapped block rotation) vs "gather" (exchange-then-GEMM);
    ``None`` defers to ``REPRO_EP_SCHEDULE`` then the planner.  Returns
    (T, F)."""
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    backend = backend or _backend()
    x_p, t, pad_t = _ep_prepare(x, w, mesh, axis)
    axes, schedule, method = _ep_executor_args(x_p, w, out_dtype, mesh,
                                               axis, schedule)
    offs = group_offsets.astype(jnp.int32)

    def run(sched):
        fn = _ep_ragged_fn(mesh, axes, out_dtype.name, backend, sched,
                           method)
        out = fn(x_p, w, offs)
        return out[:t] if pad_t else out

    return _ep_ladder(run, schedule,
                      lambda: ragged_matmul(x, w, offs, out_dtype=out_dtype,
                                            backend=backend))


def ep_ragged_swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                     group_offsets: jax.Array, *, mesh: Mesh, axis="data",
                     out_dtype=None, backend: str | None = None,
                     schedule: str | None = None) -> jax.Array:
    """Expert-parallel fused ragged MoE front half: silu(x @ Wg_g) * (x @
    Wu_g) per group with the gate/up panels expert-sharded over
    ``mesh[axis]`` — ONE token exchange each way for the fused pair (same
    contract as ``ragged_swiglu``; ``schedule`` as in
    ``ep_ragged_matmul``)."""
    if w_gate.shape != w_up.shape:
        raise ValueError((w_gate.shape, w_up.shape))
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    backend = backend or _backend()
    x_p, t, pad_t = _ep_prepare(x, w_gate, mesh, axis)
    axes, schedule, method = _ep_executor_args(x_p, w_gate, out_dtype, mesh,
                                               axis, schedule)
    offs = group_offsets.astype(jnp.int32)

    def run(sched):
        fn = _ep_ragged_swiglu_fn(mesh, axes, out_dtype.name, backend,
                                  sched, method)
        out = fn(x_p, w_gate, w_up, offs)
        return out[:t] if pad_t else out

    return _ep_ladder(run, schedule,
                      lambda: ragged_swiglu(x, w_gate, w_up, offs,
                                            out_dtype=out_dtype,
                                            backend=backend))


def ep_ragged_moe(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                  w_down: jax.Array, group_offsets: jax.Array, *,
                  mesh: Mesh, axis="data", out_dtype=None,
                  backend: str | None = None,
                  schedule: str | None = None) -> jax.Array:
    """Whole expert-parallel ragged MoE MLP in one placement:
    (silu(x @ Wg_g) * (x @ Wu_g)) @ Wd_g per group, all three panel sets
    expert-sharded over ``mesh[axis]``.  Tokens cross the axis exactly once
    each way (d_model wide); the (rows, d_ff) hidden never does — composing
    ``ep_ragged_swiglu`` + ``ep_ragged_matmul`` would exchange it twice for
    nothing, since both key off the same ``group_offsets`` windows.
    ``x`` (T, D), ``w_gate``/``w_up`` (G, D, F), ``w_down`` (G, F, D);
    ``schedule`` as in ``ep_ragged_matmul``.  Returns (T, D)."""
    if w_gate.shape != w_up.shape:
        raise ValueError((w_gate.shape, w_up.shape))
    if w_down.ndim != 3 or w_down.shape[0] != w_gate.shape[0] \
            or w_down.shape[1] != w_gate.shape[2]:
        raise ValueError((w_gate.shape, w_down.shape))
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    backend = backend or _backend()
    x_p, t, pad_t = _ep_prepare(x, w_gate, mesh, axis)
    axes, schedule, method = _ep_executor_args(x_p, w_gate, out_dtype, mesh,
                                               axis, schedule)
    offs = group_offsets.astype(jnp.int32)

    def run(sched):
        fn = _ep_ragged_moe_fn(mesh, axes, out_dtype.name, backend, sched,
                               method)
        out = fn(x_p, w_gate, w_up, w_down, offs)
        return out[:t] if pad_t else out

    def single():
        h = ragged_swiglu(x, w_gate, w_up, offs, backend=backend)
        return ragged_matmul(h, w_down, offs, out_dtype=out_dtype,
                             backend=backend)

    return _ep_ladder(run, schedule, single)
