"""The decoder models of the benchmark: Qwen3 (dense, GQA with RMSNorm on q
and k per head, RoPE, SwiGLU MLP) and Mixtral (arXiv:2401.04088: GQA, RoPE,
eight SwiGLU experts of which the router's softmax picks two per token,
gates renormalised over the two).  A configuration file without a
``"model"`` key is served by this module.

A model module (``bench/models/<name>.py``, named by a configuration file's
``"model"`` key and loaded by ``bench/spec.py``) holds everything of the
benchmark that depends on the model's layers.  The harness calls only
these names:

* ``tree(cfg, key) -> params``: the seeded weight tree in the layout the
  program serves, traced under one ``jax.jit`` by ``bench/weights.py``.
  The program and the reference both read it; neither makes weights.
* ``model_keys(cfg) -> tuple``: the configuration's sizes as hashable
  ``(key, value)`` pairs, the static ``cfg_items`` of ``forward``.
* ``forward(params, tokens, cfg_items, precision) -> (logits, margin)``:
  the plain reference, jitted with ``cfg_items`` and ``precision`` static:
  (S, vocab) next-token logits at every position of ``tokens`` (S,) and
  (S,) the smallest router margin over the layers (``inf`` where the model
  has no router).  ``"f32"`` is float32 at ``Precision.HIGHEST``;
  ``"bf16"`` rounds every matmul operand to bfloat16 (the witness);
  ``"fp8"`` rounds them to float8 e4m3 (the control).  It imports nothing
  of the program under test.
* ``prefill_model_flops(cfg, prompt_len)``, ``decode_model_flops(cfg,
  depth)``: model FLOPs of real tokens (``metrics/serve_mfu.py``);
  ``prefill_gemms(cfg, slots, bucket)``, ``decode_gemms(cfg, slots,
  view_len)``: (flops, bytes) of every GEMM one engine call runs at its
  called shapes (``metrics/gemm_roofline.py``).
* ``warm_insert(engine, cfg, bucket, lengths)``: compile and load the
  program's per-prompt-length state insert for the prompt ``lengths`` of
  one prefill ``bucket`` (``harness.warm_up``).

``cfg`` is always the configuration file's dict.  Shared pieces live in
``bench/reference/model.py`` (plain float32 layers), ``bench/flops.py``
(GEMM and roofline arithmetic) and ``bench/weights.py`` (seeds, vocabulary
padding).

Departures of the reference from the published models, the same as the
served program's:

* the output head is the transposed embedding (tied); Mixtral unties it;
* RMSNorm scales are stored as offsets from one: ``x * (1 + scale)``;
* ``rms_norm_eps`` is the configuration file's (1e-6 for both models).

Weights follow the usual fan-in initialisation (normal, variance
2/fan_in; embedding 0.02).  RMSNorm scales, stored as offsets from one,
are drawn with standard deviation 0.1 so that a norm whose scale is
ignored shows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.flops import ELT, PREFILL_KV_BLOCK, gemm
from bench.reference.model import _mm, attention, moe, rms_norm, swiglu
from bench.weights import padded_vocab


def tree(cfg: dict, key) -> dict:
    dt = jnp.dtype(cfg["param_dtype"])
    d, f, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    n_l, h, kvh = cfg["num_layers"], cfg["num_heads"], cfg["num_kv_heads"]
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(keys), shape, dt) * std

    def norm(shape):
        return normal(shape, 0.1)

    attn = {"wq": normal((n_l, d, h * hd), math.sqrt(2 / d)),
            "wk": normal((n_l, d, kvh * hd), math.sqrt(2 / d)),
            "wv": normal((n_l, d, kvh * hd), math.sqrt(2 / d)),
            "wo": normal((n_l, h * hd, d), math.sqrt(2 / (h * hd)))}
    if cfg["qk_norm"]:
        attn["q_norm"] = norm((n_l, hd))
        attn["k_norm"] = norm((n_l, hd))
    layers = {"ln1": norm((n_l, d)), "attn": attn, "ln2": norm((n_l, d))}
    e = cfg.get("num_experts", 0)
    if e:
        layers["moe"] = {
            "router": normal((n_l, d, e), math.sqrt(2 / d)),
            "w_gate": normal((n_l, e, d, f), math.sqrt(2 / d)),
            "w_up": normal((n_l, e, d, f), math.sqrt(2 / d)),
            "w_down": normal((n_l, e, f, d), math.sqrt(2 / f))}
    else:
        layers["mlp"] = {
            "w_gate": normal((n_l, d, f), math.sqrt(2 / d)),
            "w_up": normal((n_l, d, f), math.sqrt(2 / d)),
            "w_down": normal((n_l, f, d), math.sqrt(2 / f))}
    return {"embed": normal((padded_vocab(cfg), d), 0.02),
            "final_norm": norm((d,)), "layers": layers}


def model_keys(cfg: dict) -> tuple:
    """The configuration's sizes as a hashable tuple for ``forward``."""
    keys = ("num_layers", "num_heads", "num_kv_heads", "head_dim",
            "vocab_size", "qk_norm", "rope_theta", "rms_norm_eps",
            "num_experts", "top_k")
    items = [(k, cfg.get(k, 0)) for k in keys]
    items.append(("window_pattern", tuple(cfg.get("window_pattern", [0]))))
    return tuple(items)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def forward(params, tokens, cfg_items, precision):
    """(S, vocab) logits and (S,) the smallest router margin over the
    layers (infinite for a dense model)."""
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    pattern = cfg["window_pattern"]
    windows = jnp.asarray([pattern[i % len(pattern)]
                           for i in range(cfg["num_layers"])], jnp.int32)

    def layer(carry, xs):
        h, margin = carry
        p, window = xs
        x = rms_norm(h, p["ln1"], eps)
        h = h + attention(x, p["attn"], cfg, window, precision)
        x = rms_norm(h, p["ln2"], eps)
        if "moe" in p:
            y, m = moe(x, p["moe"], cfg, precision)
            return (h + y, jnp.minimum(margin, m)), None
        return (h + swiglu(x, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                           p["mlp"]["w_down"], precision), margin), None

    h = params["embed"][tokens].astype(jnp.float32)
    margin = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    (h, margin), _ = jax.lax.scan(layer, (h, margin),
                                  (params["layers"], windows))
    h = rms_norm(h, params["final_norm"], eps)
    return _mm("sd,vd->sv", h, params["embed"][:cfg["vocab_size"]],
               precision), margin


# Operations, from shapes alone.  Model FLOPs (the per-token arithmetic of
# ``repro.roofline.perf_model``, copied): every projection at 2 FLOPs per
# multiply-add, attention scores and values over the keys the token sees,
# the output head only where a logit row is read; no padding counts.
# GEMMs: every product one engine call runs at its called shapes, padding
# rows and the prefill's key blocks included, since the kernels do that
# work; routed expert GEMMs read each expert's panels once, and at most as
# many experts as there are routed rows.

def _proj_params(cfg: dict) -> int:
    d, hd = cfg["d_model"], cfg["head_dim"]
    h, kvh = cfg["num_heads"], cfg["num_kv_heads"]
    return d * (h + 2 * kvh) * hd + h * hd * d


def _mlp_active_params(cfg: dict) -> int:
    d, f = cfg["d_model"], cfg["d_ff"]
    e = cfg.get("num_experts", 0)
    if e:
        return cfg["top_k"] * 3 * d * f + d * e
    return 3 * d * f


def _keys_seen(cfg: dict, ctx: float) -> float:
    w = max(cfg.get("window_pattern", [0]))
    return min(ctx, w) if w > 0 else ctx


def token_flops(cfg: dict, ctx: float) -> float:
    """FLOPs of one token through the layers, attending to ``ctx`` keys
    (itself included); no output head."""
    per_layer = 2 * (_proj_params(cfg) + _mlp_active_params(cfg))
    per_layer += 4 * cfg["num_heads"] * cfg["head_dim"] * _keys_seen(cfg, ctx)
    return cfg["num_layers"] * per_layer


def head_flops(cfg: dict) -> float:
    """FLOPs of one row of logits."""
    return 2 * cfg["d_model"] * cfg["vocab_size"]


def prefill_model_flops(cfg: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens and its one row of logits."""
    n_l, h, hd = cfg["num_layers"], cfg["num_heads"], cfg["head_dim"]
    dense = 2 * (_proj_params(cfg) + _mlp_active_params(cfg)) * n_l
    w = max(cfg.get("window_pattern", [0]))
    p = prompt_len
    keys = (p * (p + 1) / 2 if w <= 0 or p <= w
            else w * (w + 1) / 2 + (p - w) * w)
    return dense * prompt_len + 4 * h * hd * keys * n_l + head_flops(cfg)


def decode_model_flops(cfg: dict, depth: int) -> float:
    """One generated token written at position ``depth`` (0-based)."""
    return token_flops(cfg, depth + 1) + head_flops(cfg)


def _layer_gemms(cfg: dict, rows: int) -> list[tuple[float, float]]:
    d, f, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    h, kvh = cfg["num_heads"], cfg["num_kv_heads"]
    out = [gemm(rows, d, h * hd), gemm(rows, d, kvh * hd),
           gemm(rows, d, kvh * hd), gemm(rows, h * hd, d)]
    e = cfg.get("num_experts", 0)
    if e:
        routed = rows * cfg["top_k"]
        held = min(e, routed)
        out.append(gemm(rows, d, e))
        out.append(gemm(routed, d, 2 * f, b_bytes=held * 2 * d * f * ELT))
        out.append(gemm(routed, f, d, b_bytes=held * f * d * ELT))
    else:
        out.append(gemm(rows, d, 2 * f))       # gate and up in one launch
        out.append(gemm(rows, f, d))
    return out


def _attn_gemms(cfg: dict, batch: int, q_rows: int, keys: int):
    """Scores and values of ``batch`` sequences, ``q_rows`` queries each,
    over ``keys`` keys: per (sequence, kv head) one product each way."""
    hd, kvh = cfg["head_dim"], cfg["num_kv_heads"]
    g = cfg["num_heads"] // kvh
    bh = batch * kvh
    return [gemm(q_rows * g, hd, keys, batch=bh),
            gemm(q_rows * g, keys, hd, batch=bh)]


def prefill_gemms(cfg: dict, slots: int, bucket: int):
    """GEMMs of one bucketed prefill: ``slots`` rows of ``bucket`` tokens."""
    keys = -(-bucket // PREFILL_KV_BLOCK) * PREFILL_KV_BLOCK
    per_layer = (_layer_gemms(cfg, slots * bucket)
                 + _attn_gemms(cfg, slots, bucket, keys))
    head = gemm(slots, cfg["d_model"], cfg["vocab_size"])
    return per_layer * cfg["num_layers"] + [head]


def decode_gemms(cfg: dict, slots: int, view_len: int):
    """GEMMs of one decode tick of ``slots`` rows over a paged view of
    ``view_len`` keys per slot."""
    per_layer = _layer_gemms(cfg, slots) + _attn_gemms(cfg, slots, 1,
                                                       view_len)
    head = gemm(slots, cfg["d_model"], cfg["vocab_size"])
    return per_layer * cfg["num_layers"] + [head]


def warm_insert(engine, cfg: dict, bucket: int, lengths) -> None:
    """The page insert slices and scatters at each prompt length's shape:
    one program per length of ``lengths``, all prefilled in ``bucket``."""
    from repro.models.model import make_cache
    from repro.serve.kv_pages import pages_for

    owner = "warm-up"
    cache = make_cache(engine.cfg, engine.b, bucket)
    for n in lengths:
        pages = engine.alloc.alloc(pages_for(n + 1, engine.page_size), owner)
        engine.kv.insert(0, pages, cache["k"][:, 0, :n],
                         cache["v"][:, 0, :n])
        engine.alloc.free_owner(owner)
        engine.kv.clear_slot(0)
