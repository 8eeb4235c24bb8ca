"""Plain ``jax.numpy`` layers of the benchmark's references, in float32 at
"highest" matmul precision: no kernels, no cache, no batching.  A model
module (``bench/models/<name>.py``) builds its forward pass from these and
from layers of its own.

``precision="fp8"`` is the correctness control: every matmul operand is
rounded to float8 e4m3 with a per-tensor scale before an exact product,
one precision step below the bfloat16 the configurations state.
``precision="bf16"`` rounds every matmul operand to bfloat16, the
precision the configurations state: a witness of what rounding alone does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                       # largest finite float8_e4m3fn


def fp8_round(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under a per-tensor scale, in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq: str, a, b, precision: str):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "fp8":
        a, b = fp8_round(a), fp8_round(b)
    elif precision == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def rope(x, positions, theta):
    """Rotary embedding, rotate-half convention; x (S, heads, hd)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv_freq      # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, cfg: dict, window: int, precision: str):
    s = x.shape[0]
    h, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = _mm("sd,dn->sn", x, p["wq"], precision).reshape(s, h, hd)
    k = _mm("sd,dn->sn", x, p["wk"], precision).reshape(s, kvh, hd)
    v = _mm("sd,dn->sn", x, p["wv"], precision).reshape(s, kvh, hd)
    if cfg["qk_norm"]:
        q = rms_norm(q, p["q_norm"], eps)
        k = rms_norm(k, p["k_norm"], eps)
    pos = jnp.arange(s)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    # Query head i reads key/value head i // (h // kvh).
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    scores = _mm("qhd,khd->hqk", q, k, precision) / jnp.sqrt(jnp.float32(hd))
    allowed = pos[None, :] <= pos[:, None]
    allowed &= (window <= 0) | (pos[None, :] > pos[:, None] - window)
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _mm("hqk,khd->qhd", probs, v, precision).reshape(s, h * hd)
    return _mm("sn,nd->sd", out, p["wo"], precision)


def swiglu(x, w_gate, w_up, w_down, precision):
    g = _mm("sd,df->sf", x, w_gate, precision)
    u = _mm("sd,df->sf", x, w_up, precision)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, w_down, precision)


def moe(x, p, cfg: dict, precision: str):
    """Top-k of the router's softmax, gates renormalised over the k picked;
    every expert is computed for every token and weighted by its gate,
    which is zero for the experts not picked.  Also returns each token's
    router margin: the k-th probability less the (k+1)-th, how near the
    token lies to another choice of experts."""
    probs = jax.nn.softmax(_mm("sd,de->se", x, p["router"], precision), -1)
    k = cfg["top_k"]
    ranked = jax.lax.top_k(probs, min(k + 1, cfg["num_experts"]))[0]
    margin = (ranked[:, k - 1] - ranked[:, k] if k < cfg["num_experts"]
              else jnp.full(x.shape[:1], jnp.inf))
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)        # (S, E)
    out = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        y = swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], precision)
        out = out + gates[:, e:e + 1] * y
    return out, margin
