"""A model module brought by a test configuration (``"model":
"toy_decoder"``): the decoders' served layout and layers, written apart
from ``bench/models/decoder.py`` from the shared layers alone, with its own
operation counts.  ``CALLS`` counts each call the harness makes into it
(``tree`` and ``forward`` while they trace).  ``PLANTED = True`` ignores
the second RMSNorm's scale in every layer of the reference: a forward that
departs from the served model, which the check has to catch."""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp

from bench.flops import gemm
from bench.reference.model import _mm, attention, moe, rms_norm, swiglu
from bench.weights import padded_vocab

PLANTED = False
CALLS: collections.Counter = collections.Counter()


def tree(cfg: dict, key) -> dict:
    CALLS["tree"] += 1
    dt = jnp.dtype(cfg["param_dtype"])
    d, f, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    n_l, h, kvh = cfg["num_layers"], cfg["num_heads"], cfg["num_kv_heads"]
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(keys), shape, dt) * std

    attn = {"wq": normal((n_l, d, h * hd), math.sqrt(2 / d)),
            "wk": normal((n_l, d, kvh * hd), math.sqrt(2 / d)),
            "wv": normal((n_l, d, kvh * hd), math.sqrt(2 / d)),
            "wo": normal((n_l, h * hd, d), math.sqrt(2 / (h * hd)))}
    if cfg["qk_norm"]:
        attn["q_norm"] = normal((n_l, hd), 0.1)
        attn["k_norm"] = normal((n_l, hd), 0.1)
    layers = {"ln1": normal((n_l, d), 0.1), "attn": attn,
              "ln2": normal((n_l, d), 0.1)}
    e = cfg.get("num_experts", 0)
    ffn = {"w_gate": ((e, d, f) if e else (d, f), d),
           "w_up": ((e, d, f) if e else (d, f), d),
           "w_down": ((e, f, d) if e else (f, d), f)}
    if e:
        layers["moe"] = {"router": normal((n_l, d, e), math.sqrt(2 / d))}
    block = layers.setdefault("moe" if e else "mlp", {})
    for name, (shape, fan_in) in ffn.items():
        block[name] = normal((n_l, *shape), math.sqrt(2 / fan_in))
    return {"embed": normal((padded_vocab(cfg), d), 0.02),
            "final_norm": normal((d,), 0.1), "layers": layers}


def model_keys(cfg: dict) -> tuple:
    CALLS["model_keys"] += 1
    keys = ("num_layers", "num_heads", "num_kv_heads", "head_dim",
            "vocab_size", "qk_norm", "rope_theta", "rms_norm_eps",
            "num_experts", "top_k")
    return tuple((k, cfg.get(k, 0)) for k in keys)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def forward(params, tokens, cfg_items, precision):
    CALLS["forward"] += 1
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    h = params["embed"][tokens].astype(jnp.float32)
    margin = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    for i in range(cfg["num_layers"]):
        p = jax.tree.map(lambda w, i=i: w[i], params["layers"])
        h = h + attention(rms_norm(h, p["ln1"], eps), p["attn"], cfg, 0,
                          precision)
        x = rms_norm(h, 0.0 * p["ln2"] if PLANTED else p["ln2"], eps)
        if "moe" in p:
            y, m = moe(x, p["moe"], cfg, precision)
            margin = jnp.minimum(margin, m)
        else:
            y = swiglu(x, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"], precision)
        h = h + y
    h = rms_norm(h, params["final_norm"], eps)
    return _mm("sd,vd->sv", h, params["embed"][:cfg["vocab_size"]],
               precision), margin


def _weights_read(cfg: dict) -> int:
    """Weights a token reads outside the embedding, experts at top-k."""
    d, f, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    attn = d * (2 * cfg["num_heads"] + 2 * cfg["num_kv_heads"]) * hd
    ffn = 3 * d * f * cfg.get("top_k", 1)
    return cfg["num_layers"] * (attn + ffn)


def prefill_model_flops(cfg: dict, prompt_len: int) -> float:
    CALLS["prefill_model_flops"] += 1
    return 2.0 * _weights_read(cfg) * prompt_len


def decode_model_flops(cfg: dict, depth: int) -> float:
    CALLS["decode_model_flops"] += 1
    return 2.0 * _weights_read(cfg)


def prefill_gemms(cfg: dict, slots: int, bucket: int):
    CALLS["prefill_gemms"] += 1
    return [gemm(slots * bucket, cfg["d_model"], cfg["d_model"])]


def decode_gemms(cfg: dict, slots: int, view_len: int):
    CALLS["decode_gemms"] += 1
    return [gemm(slots, cfg["d_model"], cfg["d_model"])]


def warm_insert(engine, cfg: dict, bucket: int, lengths) -> None:
    CALLS["warm_insert"] += 1
    from repro.models.model import make_cache
    from repro.serve.kv_pages import pages_for

    cache = make_cache(engine.cfg, engine.b, bucket)
    for n in lengths:
        pages = engine.alloc.alloc(pages_for(n + 1, engine.page_size), "toy")
        engine.kv.insert(0, pages, cache["k"][:, 0, :n],
                         cache["v"][:, 0, :n])
        engine.alloc.free_owner("toy")
        engine.kv.clear_slot(0)
