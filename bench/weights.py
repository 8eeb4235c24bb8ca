"""Seeded weights in the served layout, made on the device in one jitted
call.  They are the benchmark's data: the program and the reference both
read them, and neither makes them.

Scales follow the usual fan-in initialisation (normal, variance 2/fan_in;
embedding 0.02).  RMSNorm scales, stored as offsets from one, are drawn
with standard deviation 0.1 so that a norm whose scale is ignored shows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def prng_key(seed: int) -> jax.Array:
    """A key from a seed of any size (``PRNGKey`` keeps only 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def padded_vocab(cfg: dict) -> int:
    m = cfg.get("vocab_pad_multiple", 16)
    return -(-cfg["vocab_size"] // m) * m


def _tree(cfg: dict, key) -> dict:
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


def make_weights(cfg: dict, seed: int) -> dict:
    """The weight tree of ``cfg`` (a configuration file's dict) from
    ``seed``, generated on the default device."""
    return jax.jit(functools.partial(_tree, cfg))(prng_key(seed))
