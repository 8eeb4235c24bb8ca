"""Mixture-of-Experts MLP with two dispatch modes: static capacity and
ragged (capacity-free).

Two of the paper's irregular GEMM types appear here as first-class hot spots:

  * the router ``tokens x d_model x num_experts`` is T1 exactly — N = 8..16
    experts is far inside the paper's N <= 96 regime;
  * each expert's (rows x d_model x d_ff/TP) GEMMs are T3 per shard, and the
    backward dW contracts the token dim — the paper's T2 shape per expert.

Dispatch modes (``dispatch=`` / ``ModelConfig.moe_dispatch``):

``"capacity"`` — Switch-style static capacity: routed tokens scatter-pack
into an (E, C, D) buffer (tokens beyond capacity are DROPPED, padding rows
where an expert underflows), expert GEMMs run as padded grouped ftIMM GEMMs.
Shapes are fully static, so this is the jit-friendly oracle the ragged path
is validated against in the undropped regime — but the padding erases the
per-expert irregularity: every expert is priced at C = max rows regardless
of what the router actually did.

``"ragged"`` — megablocks-style capacity-free dispatch: tokens sort by
expert, per-expert counts become a ``group_offsets`` prefix-sum array, and
the expert GEMMs run as *ragged* grouped ftIMM GEMMs (one flat (T*K, D)
operand, per-group weight panels, fused silu(gate)*up epilogue for the
gate/up pair).  No token is ever dropped and no row is padded to a
capacity; the CMR planner prices the actual size distribution
(``plan_ragged_gemm`` — total rows + one boundary tile per expert, not
E x max).

Expert parallelism: when the active ``DistContext`` exposes an expert axis
(``moe_ep_axis``, set by the launchers from ``launch.sharding.expert_axis``)
and the expert count divides it, the ragged path runs its whole MLP through
``core.gemm.ep_ragged_moe`` — the tokens all-to-all to the shard that owns
their expert (keyed by the same ``group_offsets`` prefix sums), the fused
silu(gate)*up and the down projection run on that shard (the d_ff-wide
hidden never crosses the axis), and the inverse exchange returns the
d_model outputs — so each chip holds and streams only its G/num_shards
expert panels.  The placement is priced by the same planner
(``plan_ragged_gemm(..., num_shards=n)`` / ``plan_moe_dispatch``) that picks
the block sizes — strategy x blocking as ONE decision, at mesh scale.

When to prefer which: the planner's ragged estimate beats the capacity
estimate whenever the router is unbalanced (capacity pads every expert to
the max) or when dropping tokens is unacceptable (training quality,
parity evals).  Capacity wins only when distributions are near-uniform AND
the fixed shapes matter more than the ~C/mean padding waste (e.g. frozen
serving graphs where recompilation dominates).  The aux loss is identical
in both modes — it depends only on router probabilities, not dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.dist import current_dist, shard_act
from ..core.gemm import (ep_ragged_moe, grouped_matmul, grouped_swiglu,
                         plan_moe_dispatch, project, ragged_matmul,
                         ragged_swiglu)


def _ep_axis(num_experts: int):
    """The mesh axis carrying the expert dim, when the active DistContext
    exposes one (``launch.sharding.expert_axis``, which already enforces the
    divisibility rule when it knows E) and the expert count divides it —
    else None (single-device / replicated-expert semantics).  The re-check
    here only guards hand-built DistContexts."""
    ctx = current_dist()
    axis = getattr(ctx, "moe_ep_axis", None) if ctx is not None else None
    if not axis:
        return None, None
    from ..core.gemm.distributed import _axis_size
    nc = _axis_size(ctx.mesh, axis)
    if nc <= 1 or num_experts % nc:
        return None, None
    return ctx.mesh, axis


def init_moe_params(key, d_model: int, d_ff: int, num_experts: int,
                    dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    s_in = (2.0 / d_model) ** 0.5
    s_out = (2.0 / d_ff) ** 0.5
    return {
        "router": jax.random.normal(ks[0], (d_model, num_experts), dtype) * s_in,
        "w_gate": jax.random.normal(ks[1], (num_experts, d_model, d_ff), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (num_experts, d_model, d_ff), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (num_experts, d_ff, d_model), dtype) * s_out,
    }


def capacity(num_tokens: int, num_experts: int, top_k: int,
             capacity_factor: float = 1.25, dtype=jnp.float32) -> int:
    """Per-expert capacity, padded to the *dtype-dependent* sublane multiple.

    The expert GEMM's M dim is the capacity, so it must align to the register
    tile: (8,128) fp32 but (16,128) bf16 — a hardcoded 8 under-pads bf16
    buffers (the same bug class PR 1 fixed in ftimm/ops.py).  Delegates to
    the planner's ``plan_moe_dispatch`` (rows == E x capacity) so the
    runtime dispatch buffer and the roofline's priced rows share ONE
    rounding rule and can never diverge."""
    rows = plan_moe_dispatch(
        num_tokens, num_experts, top_k, 0, 0, dispatch="capacity",
        capacity_factor=capacity_factor,
        elt_bytes=jnp.dtype(dtype).itemsize).rows
    return rows // num_experts


def _router(x: jax.Array, params: dict, num_experts: int, top_k: int):
    """Shared router head: T1 GEMM + top-k gates + Switch-style aux loss."""
    with jax.named_scope("router"):
        with jax.named_scope("cast"):
            w = params["router"].astype(x.dtype)
        logits = project(x, w, out_dtype=jnp.float32)            # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_idx = jax.lax.top_k(probs, top_k)           # (T, K)
        if top_k > 1:
            gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        one_hot = jax.nn.one_hot(gate_idx[:, 0], num_experts)
        ce = jnp.mean(one_hot, axis=0)
        aux = num_experts * jnp.sum(me * ce)
    return gate_w, gate_idx, aux


def _expert_weights(params: dict, compute_dtype):
    """The gate, up and down expert panels in the compute dtype."""
    with jax.named_scope("cast"):
        return tuple(params[k].astype(compute_dtype)
                     for k in ("w_gate", "w_up", "w_down"))


def moe_mlp(
    x: jax.Array,                  # (T, D) flat tokens
    params: dict,
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    compute_dtype=jnp.bfloat16,
    dispatch: str = "capacity",    # "capacity" | "ragged"
    quant: str | None = None,      # core.quant mode for the expert panels
) -> tuple[jax.Array, jax.Array]:
    """Returns (output (T, D), aux_loss scalar).  See module docstring for
    the two dispatch modes; ``capacity_factor`` is ignored by "ragged".

    ``quant`` (a ``core.quant`` mode — "w8"/"w4"/"int8"/...) runs the
    ragged expert GEMMs with quantized per-expert panels: per-expert
    per-channel scales fused at the accumulator flush, straight-through
    backward against the dequantized panels.  Zero-drop int8 experts —
    ragged dispatch only (the capacity path pads and drops; quantizing it
    would conflate two approximations in one parity story)."""
    from ..core import quant as _quant
    qcfg = _quant.resolve(quant)
    if dispatch == "ragged":
        return _moe_mlp_ragged(x, params, num_experts=num_experts,
                               top_k=top_k, compute_dtype=compute_dtype,
                               qcfg=qcfg)
    if dispatch != "capacity":
        raise ValueError(f"unknown moe dispatch: {dispatch}")
    if not qcfg.is_noop:
        raise ValueError("quantized experts require the ragged (zero-drop) "
                         f"dispatch, not {dispatch!r}")
    t, d = x.shape
    e = num_experts
    c = capacity(t, e, top_k, capacity_factor, dtype=compute_dtype)
    xc = x.astype(compute_dtype)

    gate_w, gate_idx, aux = _router(xc, params, e, top_k)

    with jax.named_scope("dispatch"):
        # Position of each (token, k) within its expert's capacity bucket.
        flat_idx = gate_idx.reshape(-1)                          # (T*K,)
        sel = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)       # (T*K, E)
        pos_in_e = jnp.cumsum(sel, axis=0) - 1          # rank within expert
        pos = jnp.take_along_axis(pos_in_e, flat_idx[:, None],
                                  axis=1)[:, 0]
        keep = pos < c
        slot = jnp.where(keep, flat_idx * c + pos, e * c)        # drop -> OOB

        # Scatter-pack tokens into the (E*C, D) buffer (paper: each "core"
        # receives its private A panel).
        tok_idx = jnp.repeat(jnp.arange(t), top_k)
        buf = jnp.zeros((e * c, d), compute_dtype)
        buf = buf.at[slot].add(xc[tok_idx], mode="drop")
        buf = buf.reshape(e, c, d)
        ctx = current_dist()
        if ctx is not None and ctx.moe_buf_shard:
            # dispatch buffers replicated by default (GSPMD scatter
            # inference); shard capacity over dp — the paper's "each core
            # owns its private A panel" at the MoE level
            buf = shard_act(buf, None, "dp", None)

    # Expert GEMMs (T3 per shard): grouped ftIMM GEMMs (E, C, D) @ (E, D, F)
    # through the CMR planner — the batch dim is the expert index, the
    # per-expert shape is the paper's irregular (capacity x d_model x d_ff);
    # their backward dW is the T2-shaped grouped GEMM, planned the same way.
    # The gate/up pair is ONE fused silu(gate)*up launch (the capacity-mode
    # analogue of the ragged path's fused SwiGLU).
    with jax.named_scope("experts"):
        wg, wu, wd = _expert_weights(params, compute_dtype)
        h = grouped_swiglu(buf, wg, wu)
        y_buf = grouped_matmul(h, wd).reshape(e * c, d)

    with jax.named_scope("combine"):
        # Gather back and combine with gate weights.
        y_tok = jnp.take(y_buf, jnp.minimum(slot, e * c - 1), axis=0)
        y_tok = y_tok * (keep * gate_w.reshape(-1))[:, None].astype(
            compute_dtype)
        y = jnp.sum(y_tok.reshape(t, top_k, d), axis=1)
    return y.astype(x.dtype), aux


def _moe_mlp_ragged(
    x: jax.Array,                  # (T, D) flat tokens
    params: dict,
    *,
    num_experts: int,
    top_k: int,
    compute_dtype=jnp.bfloat16,
    qcfg=None,
) -> tuple[jax.Array, jax.Array]:
    """Capacity-free dispatch: sort-by-expert + prefix-sum offsets.

    Every routed (token, k) copy is kept — per-expert row counts become the
    ragged M dims of the grouped ftIMM GEMMs (the irregular shapes the CMR
    planner exists to exploit), and the gate/up pair runs as ONE fused
    silu(gate)*up kernel launch.

    ``qcfg`` (a non-noop ``core.quant.QuantConfig``) swaps the expert GEMMs
    for their quantized ragged forms: gate/up/down each stream int8 (or
    int4/fp8) per-expert panels with the dequant fused at the flush; the
    silu*mul runs elementwise between them (the fused-SwiGLU kernel stays
    full-precision-only — its two panels would need two scale vectors in
    one flush).  The router is NEVER quantized (T1 is tiny and gate
    fidelity is the whole zero-drop story).  Expert-parallel meshes keep
    full-precision panels: the EP pipeline fuses its own exchange."""
    t, d = x.shape
    e = num_experts
    xc = x.astype(compute_dtype)

    gate_w, gate_idx, aux = _router(xc, params, e, top_k)

    # Sort the (T*K,) routed copies by expert id (stable: ties keep token
    # order) and build the per-expert prefix sums — the dynamic group sizes.
    with jax.named_scope("dispatch"):
        flat_idx = gate_idx.reshape(-1)                          # (T*K,)
        order = jnp.argsort(flat_idx)                            # stable
        tok_sorted = order // top_k                      # token of slot
        counts = jnp.zeros((e,), jnp.int32).at[flat_idx].add(1)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]
        ).astype(jnp.int32)
        xs = jnp.take(xc, tok_sorted, axis=0)                    # (T*K, D)

    # Ragged expert GEMMs through the CMR planner: fused gate/up, then down.
    # When the sharding layout exposes an expert axis on the mesh
    # (DistContext.moe_ep_axis), the same GEMMs run expert-parallel: tokens
    # all-to-all to the shard owning their expert (keyed by the very same
    # ``offsets`` prefix sums), G/num_shards panels per shard, inverse
    # exchange on the way back — instead of every chip replicating every
    # expert panel.
    with jax.named_scope("experts"):
        wg, wu, wd = _expert_weights(params, compute_dtype)
        mesh, ep_axis = _ep_axis(e)
        if ep_axis is not None:
            # Fused EP pipeline: one d_model-wide exchange each way; the
            # (rows, d_ff) hidden stays on the shard owning the expert.
            # (Quantized panels deliberately not routed here: the exchange
            # moves activations, not panels, so quant buys no wire bytes.)
            ys = ep_ragged_moe(xs, wg, wu, wd, offsets, mesh=mesh,
                               axis=ep_axis)
        elif qcfg is not None and not qcfg.is_noop:
            hg = ragged_matmul(xs, wg, offsets, quant=qcfg,
                               out_dtype=jnp.float32)            # (T*K, F)
            hu = ragged_matmul(xs, wu, offsets, quant=qcfg,
                               out_dtype=jnp.float32)
            h = (jax.nn.silu(hg) * hu).astype(compute_dtype)
            ys = ragged_matmul(h, wd, offsets, quant=qcfg)       # (T*K, D)
        else:
            h = ragged_swiglu(xs, wg, wu, offsets)               # (T*K, F)
            ys = ragged_matmul(h, wd, offsets)                   # (T*K, D)

    with jax.named_scope("combine"):
        # Un-sort and combine with gate weights (every copy kept — no
        # drops).
        gw_sorted = jnp.take(gate_w.reshape(-1), order)
        y = jnp.zeros((t, d), compute_dtype).at[tok_sorted].add(
            ys * gw_sorted[:, None].astype(compute_dtype))
    return y.astype(x.dtype), aux
