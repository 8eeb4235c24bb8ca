"""Collective exchange layer for the mesh executors: the ragged all-to-all
and the overlapped (ring) collective-GEMM schedules.

The EP token exchange is keyed by the ``group_offsets`` prefix sums: rows
arrive sorted by group and experts are contiguously owned by shards, so
shard s owns the contiguous window [offsets[s*G_l], offsets[(s+1)*G_l)) of
the global row array.  This module realizes that exchange two ways and the
surrounding GEMM two ways:

**Exchange realizations** (``exchange_method``):

  * ``"primitive"`` — ``jax.lax.ragged_all_to_all`` (backend support
    varies): each shard ships ONLY the bytes of the owned windows,
    send/recv offsets derived from the prefix sums.  Availability of the
    symbol is necessary but not sufficient — a concrete round-trip probe on
    the actual mesh must pass before it is trusted (``REPRO_RAGGED_A2A=auto``,
    the default; ``=primitive`` forces, ``=dense`` disables).
  * ``"dense"`` — the portable realization: one ``all_gather`` of the rows
    in, a scatter + ``psum_scatter`` back (windows are disjoint and cover
    [0, T), so the sum just merges them).  Works on every backend; moves
    more bytes but the same number of collectives.

**Schedules** (the ``Placement.schedule`` axis the tuner prices):

  * ``"gather"`` — unoverlapped: exchange, then ONE per-shard ragged GEMM
    over the worst-case T-row window (every row could route to this shard's
    experts), then the return leg.  Simple, but the static window means
    per-shard compute is O(T) regardless of how many rows the shard owns.
  * ``"ring"`` — the overlapped collective matmul (paper §IV's DMA pipeline
    lifted to mesh scale): token blocks rotate around the ring via
    ``ppermute`` while each shard computes only the blocks that intersect
    its owned window (``lax.cond``-skipped otherwise), double-buffered by
    XLA's async collective scheduling — chunk k+1's transfer overlaps chunk
    k's compute, and per-shard compute is proportional to the rows the
    shard actually owns (~2 blocks when balanced) instead of T.

``ring_kparallel`` is the dense analogue for ``dist_matmul``: the output
columns are chunked over shard-steps, partial sums rotate around the ring
and each hop overlaps the next chunk's local GEMM.

Ring schedules require a single mesh axis (``ppermute`` permutes one named
axis); multi-axis EP requests fall back to the gather schedule.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .. import compat

ENV_A2A = "REPRO_RAGGED_A2A"
SCHEDULES = ("gather", "ring")


def mask_rows(x: jax.Array, n_valid: jax.Array) -> jax.Array:
    """Zero rows at index >= n_valid (rows past the owned window)."""
    return jnp.where(jnp.arange(x.shape[0])[:, None] < n_valid, x,
                     jnp.zeros((), x.dtype))


def owned_bounds(offsets: jax.Array, g_l: int, sidx: jax.Array):
    """This shard's slice of the prefix sums: (local offsets, start, stop)."""
    lo = jax.lax.dynamic_slice_in_dim(offsets, sidx * g_l, g_l + 1)
    return lo, lo[0], lo[g_l]


# ---------------------------------------------------------------------------
# Exchange-method selection: probe the true ragged a2a on the actual mesh.
# ---------------------------------------------------------------------------

def _probe_offsets(nc: int, tl: int):
    """A deliberately adversarial distribution for the probe: one window
    spanning several blocks, one empty window, singleton windows, ending
    exactly at T so the round-trip must reproduce the input bitwise."""
    import numpy as np
    t = nc * tl
    off = [0, t - (nc - 1)]                 # window 0 spans most rows
    for j in range(2, nc + 1):
        off.append(t - nc + j)
    off[min(2, nc)] = off[1]                # make one window empty
    return np.asarray(off, dtype=np.int32)


@functools.lru_cache(maxsize=16)
def _primitive_probe_ok(mesh: Mesh, ax: str) -> bool:
    """Run a tiny dispatch+combine round-trip through the primitive on the
    real mesh and require it to reproduce the input exactly.  Any failure
    (missing backend lowering, semantics drift, compile error) means the
    dense realization is used instead — the probe is the contract."""
    nc = int(mesh.shape[ax])
    if nc <= 1:
        return False
    tl, d = 2, 4
    import numpy as np
    offs = _probe_offsets(nc, tl)
    x = np.arange(nc * tl * d, dtype=np.float32).reshape(nc * tl, d)

    def f(x_l, o):
        win, lo, start, stop = primitive_dispatch(x_l, o, 1, ax, nc)
        return primitive_combine(mask_rows(win, stop - start), o, 1, ax, nc,
                                 tl)

    try:
        g = jax.jit(compat.shard_map_unchecked(
            f, mesh=mesh, in_specs=(P(ax, None), P(None)),
            out_specs=P(ax, None)))
        y = jax.device_get(g(x, offs))
        return bool((y == x).all())
    except Exception:
        return False


def exchange_method(mesh: Mesh, axes: tuple) -> str:
    """"primitive" when the true ragged all-to-all exists AND passes the
    round-trip probe on this mesh; "dense" otherwise.  ``REPRO_RAGGED_A2A``
    overrides: "dense" disables the probe, "primitive" makes an unusable
    primitive a hard error instead of a silent fallback."""
    return _method_cached(mesh, axes, os.environ.get(ENV_A2A, "auto"))


@functools.lru_cache(maxsize=32)
def _method_cached(mesh: Mesh, axes: tuple, env: str) -> str:
    if env == "dense":
        return "dense"
    ok = len(axes) == 1 and _primitive_probe_ok(mesh, axes[0])
    if env == "primitive" and not ok:
        raise RuntimeError(
            "REPRO_RAGGED_A2A=primitive but jax.lax.ragged_all_to_all is "
            "unavailable or failed the round-trip probe on this mesh")
    return "primitive" if ok else "dense"


# ---------------------------------------------------------------------------
# The true ragged all-to-all: send/recv geometry from the prefix sums.
# ---------------------------------------------------------------------------

def _window_bounds_all(offsets: jax.Array, g_l: int, nc: int):
    """(nc+1,) global window bounds: shard j owns [wb[j], wb[j+1])."""
    return offsets[jnp.arange(nc + 1, dtype=jnp.int32) * g_l]


def primitive_dispatch(x_l: jax.Array, offsets: jax.Array, g_l: int,
                       ax: str, nc: int):
    """Dispatch leg via ``ragged_all_to_all``: ship each contiguous run of
    my rows to the shard whose window contains it.  Returns the (T, d)
    window buffer with owned rows at [0, wlen) — the same layout the dense
    realization's window slice produces — plus (local offsets, start, stop).
    """
    tl, _d = x_l.shape
    t = nc * tl
    s = jax.lax.axis_index(ax)
    r0 = s * tl
    wb = _window_bounds_all(offsets, g_l, nc).astype(jnp.int32)
    w_lo, w_hi = wb[:-1], wb[1:]
    # To dest j: my rows ∩ j's window, placed at (global row - w_lo[j]).
    in_off = jnp.clip(w_lo - r0, 0, tl).astype(jnp.int32)
    send = jnp.clip(jnp.minimum(w_hi, r0 + tl) - jnp.maximum(w_lo, r0),
                    0, tl).astype(jnp.int32)
    out_off = jnp.clip(r0 - w_lo, 0, t).astype(jnp.int32)
    # From source i: i's rows ∩ my window.
    blk = jnp.arange(nc, dtype=jnp.int32) * tl
    lo, start, stop = owned_bounds(offsets, g_l, s)
    recv = jnp.clip(jnp.minimum(stop, blk + tl) - jnp.maximum(start, blk),
                    0, tl).astype(jnp.int32)
    buf = jnp.zeros((t,) + x_l.shape[1:], x_l.dtype)
    win = compat.ragged_all_to_all(x_l, buf, in_off, send, out_off, recv,
                                   axis_name=ax)
    return win, lo, start, stop


def primitive_combine(win_out: jax.Array, offsets: jax.Array, g_l: int,
                      ax: str, nc: int, tl: int) -> jax.Array:
    """Return leg via ``ragged_all_to_all``: the inverse geometry — my
    window rows [0, wlen) ship back to the shards owning the corresponding
    global rows.  Unowned output rows (T padding past offsets[-1]) stay
    zero, matching the psum_scatter realization."""
    t = win_out.shape[0]
    s = jax.lax.axis_index(ax)
    r0 = s * tl
    wb = _window_bounds_all(offsets, g_l, nc).astype(jnp.int32)
    w_lo, w_hi = wb[:-1], wb[1:]
    o_lo, o_hi = wb[s], wb[s + 1]
    blk = jnp.arange(nc, dtype=jnp.int32) * tl
    in_off = jnp.clip(blk - o_lo, 0, t).astype(jnp.int32)
    send = jnp.clip(jnp.minimum(o_hi, blk + tl) - jnp.maximum(o_lo, blk),
                    0, tl).astype(jnp.int32)
    out_off = jnp.clip(o_lo - blk, 0, tl).astype(jnp.int32)
    recv = jnp.clip(jnp.minimum(w_hi, r0 + tl) - jnp.maximum(w_lo, r0),
                    0, tl).astype(jnp.int32)
    buf = jnp.zeros((tl,) + win_out.shape[1:], win_out.dtype)
    return compat.ragged_all_to_all(win_out, buf, in_off, send, out_off,
                                    recv, axis_name=ax)


# ---------------------------------------------------------------------------
# Unified dispatch/combine: collective part split from the pure window
# slice, so the executors can cond-skip the slice+GEMM on empty shards
# (collectives must run unconditionally on every shard).
# ---------------------------------------------------------------------------

def dispatch_payload(x_l: jax.Array, offsets: jax.Array, g_l: int,
                     axes: tuple, ax, nc: int, method: str, sidx):
    """Run the dispatch leg's COLLECTIVE and return
    ``(payload, loffs, start, stop)``.  ``window_from_payload`` turns the
    payload into the (T, d) owned-rows window — a pure slice that callers
    wrap in the empty-shard ``lax.cond``."""
    if method == "primitive":
        return primitive_dispatch(x_l, offsets, g_l, axes[0], nc)
    full = jax.lax.all_gather(x_l, ax, axis=0, tiled=True)
    lo, start, stop = owned_bounds(offsets, g_l, sidx)
    return full, lo, start, stop


def window_from_payload(payload: jax.Array, start: jax.Array,
                        method: str) -> jax.Array:
    """Pure part of the dispatch leg: position the owned rows at [0, wlen).
    The primitive already delivered them there; the dense payload is the
    full gathered row array, sliced at ``start`` (zero-padded to keep the
    slice in range — rows past wlen are masked by the caller)."""
    if method == "primitive":
        return payload
    padded = jnp.concatenate([payload, jnp.zeros_like(payload)], axis=0)
    return jax.lax.dynamic_slice_in_dim(padded, start, payload.shape[0],
                                        axis=0)


def combine_rows(win_out: jax.Array, offsets: jax.Array, g_l: int,
                 axes: tuple, ax, nc: int, method: str, start,
                 tl: int) -> jax.Array:
    """Inverse exchange: window rows (masked past wlen by the caller) back
    to the global row-sorted layout, (tl, d) per shard."""
    if method == "primitive":
        return primitive_combine(win_out, offsets, g_l, axes[0], nc, tl)
    t = win_out.shape[0]
    buf = jnp.zeros((2 * t,) + win_out.shape[1:], win_out.dtype)
    buf = jax.lax.dynamic_update_slice_in_dim(buf, win_out, start, axis=0)
    return jax.lax.psum_scatter(buf[:t], ax, scatter_dimension=0, tiled=True)


# ---------------------------------------------------------------------------
# Ring schedules: the overlapped collective GEMM.
# ---------------------------------------------------------------------------

def ring_forward(x_l: jax.Array, offsets: jax.Array, g_l: int, ax: str,
                 nc: int, compute, out_width: int, out_dtype) -> jax.Array:
    """Overlapped EP forward: token blocks rotate around the ring; at step p
    shard s holds block b = (s - p) mod nc and computes only when b
    intersects its owned window [o_lo, o_hi) — the ``lax.cond`` skip is what
    makes per-shard compute proportional to owned rows instead of T.  The
    output block accumulates contributions as it rides the ring and arrives
    home after nc hops.  ``compute(win, loffs, run_len) -> (tl, out_width)``
    is the per-block local ragged product."""
    tl = x_l.shape[0]
    s = jax.lax.axis_index(ax)
    lo, o_lo, o_hi = owned_bounds(offsets, g_l, s)
    perm = [(j, (j + 1) % nc) for j in range(nc)]
    x_blk = x_l
    y_blk = jnp.zeros((tl, out_width), out_dtype)
    for p in range(nc):
        b0 = ((s - p) % nc) * tl
        run_lo = jnp.clip(o_lo - b0, 0, tl)
        run_hi = jnp.clip(o_hi - b0, 0, tl)
        run_len = run_hi - run_lo

        def step(x_blk=x_blk, run_lo=run_lo, run_hi=run_hi,
                 run_len=run_len, b0=b0):
            pad = jnp.concatenate([x_blk, jnp.zeros_like(x_blk)], axis=0)
            win = jax.lax.dynamic_slice_in_dim(pad, run_lo, tl, axis=0)
            loffs = (jnp.clip(lo - b0, run_lo, run_hi)
                     - run_lo).astype(jnp.int32)
            y_win = mask_rows(compute(win, loffs, run_len), run_len)
            buf = jnp.zeros((2 * tl, out_width), out_dtype)
            buf = jax.lax.dynamic_update_slice_in_dim(buf, y_win, run_lo,
                                                      axis=0)
            return buf[:tl]

        y_blk = y_blk + jax.lax.cond(
            run_len > 0, step,
            lambda: jnp.zeros((tl, out_width), out_dtype))
        if p < nc - 1:
            x_blk = jax.lax.ppermute(x_blk, ax, perm)
        y_blk = jax.lax.ppermute(y_blk, ax, perm)
    return y_blk


def ring_backward(ct_l: jax.Array, x_l: jax.Array, offsets: jax.Array,
                  g_l: int, ax: str, nc: int, compute, dw_zeros: tuple):
    """Overlapped EP backward: (cotangent, activation) blocks rotate
    TOGETHER (one fused rotation pair per hop — the ring analogue of the
    fused concatenated gather); dX contributions accumulate onto a third
    rotating block, dW accumulates locally on the shard owning the panels.
    ``compute(ct_win, x_win, loffs, run_len) -> (dx_win, (dw, ...))``;
    returns ``(dx_l, (dw, ...))``."""
    tl = x_l.shape[0]
    s = jax.lax.axis_index(ax)
    lo, o_lo, o_hi = owned_bounds(offsets, g_l, s)
    perm = [(j, (j + 1) % nc) for j in range(nc)]
    ct_blk, x_blk = ct_l, x_l
    dx_blk = jnp.zeros_like(x_l)
    dws = tuple(dw_zeros)
    for p in range(nc):
        b0 = ((s - p) % nc) * tl
        run_lo = jnp.clip(o_lo - b0, 0, tl)
        run_hi = jnp.clip(o_hi - b0, 0, tl)
        run_len = run_hi - run_lo

        def step(ct_blk=ct_blk, x_blk=x_blk, run_lo=run_lo,
                 run_hi=run_hi, run_len=run_len, b0=b0):
            def shift(blk):
                pad = jnp.concatenate([blk, jnp.zeros_like(blk)], axis=0)
                return jax.lax.dynamic_slice_in_dim(pad, run_lo, tl, axis=0)

            loffs = (jnp.clip(lo - b0, run_lo, run_hi)
                     - run_lo).astype(jnp.int32)
            dx_win, dw_c = compute(shift(ct_blk), shift(x_blk), loffs,
                                   run_len)
            dx_win = mask_rows(dx_win, run_len)
            buf = jnp.zeros((2 * tl,) + dx_win.shape[1:], dx_win.dtype)
            buf = jax.lax.dynamic_update_slice_in_dim(buf, dx_win, run_lo,
                                                      axis=0)
            return (buf[:tl],) + tuple(dw_c)

        zero = (jnp.zeros_like(x_l),) + tuple(jnp.zeros_like(z)
                                              for z in dws)
        out = jax.lax.cond(run_len > 0, step, lambda zero=zero: zero)
        dx_blk = dx_blk + out[0]
        dws = tuple(d + c for d, c in zip(dws, out[1:]))
        if p < nc - 1:
            ct_blk = jax.lax.ppermute(ct_blk, ax, perm)
            x_blk = jax.lax.ppermute(x_blk, ax, perm)
        dx_blk = jax.lax.ppermute(dx_blk, ax, perm)
    return dx_blk, dws


def ring_kparallel(a_l: jax.Array, b_l: jax.Array, ax: str, nc: int,
                   partial_fn) -> jax.Array:
    """Overlapped K-parallel collective matmul: output columns chunked over
    shard-steps.  At step p shard s computes its K-shard's partial for
    column chunk (s - p - 1) mod nc, adds the partial sum arriving from the
    ring, and forwards — chunk transfers overlap the next chunk's local
    GEMM (the mesh-level analogue of the paper's core-level DMA pipeline).
    After nc steps shard s holds the fully reduced chunk s; one tiled
    all_gather reassembles the replicated (M, N) output.  ``b_l``'s N must
    be an nc multiple (callers pad).  ``partial_fn(a_l, b_chunk)`` is the
    fp32 local GEMM."""
    n = b_l.shape[1]
    cn = n // nc
    s = jax.lax.axis_index(ax)
    perm = [(j, (j + 1) % nc) for j in range(nc)]
    acc = jnp.zeros((a_l.shape[0], cn), jnp.float32)
    for p in range(nc):
        c = (s - p - 1) % nc
        b_c = jax.lax.dynamic_slice_in_dim(b_l, c * cn, cn, axis=1)
        acc = acc + partial_fn(a_l, b_c)
        if p < nc - 1:
            acc = jax.lax.ppermute(acc, ax, perm)
    return jax.lax.all_gather(acc, ax, axis=1, tiled=True)
