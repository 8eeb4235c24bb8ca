"""Seeded weights in the served layout, made on the device in one jitted
call of the configuration's model module's ``tree``.  They are the
benchmark's data: the program and the reference both read them, and
neither makes them.
"""
from __future__ import annotations

import functools

import jax

from . import spec


def prng_key(seed: int) -> jax.Array:
    """A key from a seed of any size (``PRNGKey`` keeps only 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def padded_vocab(cfg: dict) -> int:
    m = cfg.get("vocab_pad_multiple", 16)
    return -(-cfg["vocab_size"] // m) * m


def make_weights(cfg: dict, seed: int) -> dict:
    """The weight tree of ``cfg`` (a configuration file's dict) from
    ``seed``, generated on the default device by its model module."""
    tree = spec.model_module(cfg).tree
    return jax.jit(functools.partial(tree, cfg))(prng_key(seed))
