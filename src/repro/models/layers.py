"""Shared building blocks: norms, projections, rotary embeddings, MLPs.

All dense contractions route through ``repro.core.gemm.project`` so the
ftIMM planner sees every GEMM in the framework (and dispatches to the Pallas
kernels on TPU).  Weights are kept in ``param_dtype`` (fp32 master) and cast
to ``compute_dtype`` at use, each cast under a ``cast`` named scope so the
device time it takes reads apart in a profile.

Elementwise layer tails fuse into their producing GEMM: ``dense`` takes
optional ``bias`` / ``residual`` / ``activation`` (an ``Epilogue`` applied at
the fp32 accumulator flush instead of separate XLA passes over the output),
and ``swiglu`` runs its gate/up pair as ONE fused kernel launch
(``project_swiglu``) with the residual add fused into the down projection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.dist import shard_act
from ..core.gemm import Epilogue, project, project_swiglu


def dense(x: jax.Array, w: jax.Array, compute_dtype=jnp.bfloat16, *,
          bias: jax.Array | None = None,
          residual: jax.Array | None = None,
          activation: str = "none",
          quant: str | None = None) -> jax.Array:
    """y = act(x @ w + bias) + residual with fp32 accumulation; w cast to
    compute dtype at use.  The bias/activation/residual tail (when present)
    is a fused GEMM epilogue — applied to the fp32 accumulator in VMEM, not
    as separate passes over the stored output.  ``quant`` (a ``core.quant``
    mode) routes through the managed quantized GEMM: the panel is quantized
    per channel in-trace, dequant fused at the flush, straight-through
    backward."""
    epi = Epilogue(bias=bias is not None, activation=activation,
                   residual=residual is not None)
    x = x.astype(compute_dtype)
    with jax.named_scope("cast"):
        w = w.astype(compute_dtype)
        bias = None if bias is None else bias.astype(compute_dtype)
    if epi.is_identity:
        return project(x, w, out_dtype=compute_dtype, quant=quant)
    return project(
        x, w, out_dtype=compute_dtype, epilogue=epi, bias=bias,
        residual=None if residual is None
        else residual.astype(compute_dtype), quant=quant)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    from ..core.dist import current_dist
    ctx = current_dist()
    if ctx is not None and ctx.rms_bf16:
        # Fusion-friendly form: variance reduced in f32, normalization kept
        # in the input dtype so the residual stream is never converted to a
        # full f32 tensor (XLA convert-motion otherwise stores the layer-scan
        # carries as f32 — 2x the checkpoint memory).
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        inv = (jax.lax.rsqrt(var + eps)).astype(x.dtype)
        return x * inv * (1.0 + scale.astype(x.dtype))
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
           compute_dtype=jnp.bfloat16,
           residual: jax.Array | None = None) -> jax.Array:
    """SwiGLU MLP: down(silu(gate(x)) * up(x)) [+ residual].  gate/up are
    T3-shaped GEMMs in training (tokens x d_model x d_ff), run as ONE fused
    kernel launch (x streamed once against both panels, silu(gate)*up at the
    accumulator flush); the residual add fuses into the down projection's
    epilogue instead of a separate pass over the layer output."""
    x = x.astype(compute_dtype)
    with jax.named_scope("cast"):
        w_gate = w_gate.astype(compute_dtype)
        w_up = w_up.astype(compute_dtype)
    h = project_swiglu(x, w_gate, w_up, out_dtype=compute_dtype)
    return dense(h, w_down, compute_dtype, residual=residual)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary position embedding. x: (..., S, H, D), positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    angles = angles[..., None, :]                             # (..., S, 1, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def embed(tokens: jax.Array, table: jax.Array,
          compute_dtype=jnp.bfloat16) -> jax.Array:
    with jax.named_scope("cast"):
        table = table.astype(compute_dtype)
    return table[tokens]


def unembed(x: jax.Array, table: jax.Array, vocab_size: int,
            compute_dtype=jnp.bfloat16) -> jax.Array:
    """Logits = x @ E^T over the (padded) vocab table; padded slots masked.

    The table arrives (vocab/model, d_model/dp)-sharded (ZeRO-3); constrain
    the transposed operand to (None, model) so GSPMD all-gathers the small
    D dim instead of all-reducing a (tokens x vocab) partial product."""
    with jax.named_scope("cast"):
        wt = table.astype(compute_dtype).T
    wt = shard_act(wt, None, "model")
    logits = project(x.astype(compute_dtype), wt, out_dtype=jnp.float32)
    pad = logits.shape[-1] - vocab_size
    if pad > 0:
        mask = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(mask, logits, -1e30)
    return logits


# ----------------------------- initializers -----------------------------

def he_init(key, shape, dtype=jnp.float32, fan_in=None):
    fan_in = fan_in or shape[0]
    return jax.random.normal(key, shape, dtype) * (2.0 / fan_in) ** 0.5


def zeros_init(_key, shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)
