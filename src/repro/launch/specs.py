"""Abstract (``ShapeDtypeStruct``) model inputs and state: what a step is
lowered against without allocating anything.  Importing this module leaves
the process's devices alone (``launch.dryrun`` forces 512 fake host devices
at import; the training launcher and the elastic runner must not inherit
that)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from ..models.model import init_params
from ..optim.adamw import init_opt_state


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Abstract (ShapeDtypeStruct) stand-ins for every model input."""
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        batch = {
            "tokens": sds((b, s), jnp.int32),
            "labels": sds((b, s), jnp.int32),
            "loss_mask": sds((b, s), jnp.float32),
        }
    elif shape.kind == "prefill":
        batch = {"tokens": sds((b, s), jnp.int32)}
    else:  # decode: one new token against a cache of seq_len
        batch = {"tokens": sds((b, 1), jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = sds((b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    if cfg.num_patches:
        batch["patch_embeds"] = sds((b, cfg.num_patches, cfg.d_model),
                                    jnp.bfloat16)
    return batch


def abstract_state(cfg: ModelConfig, shape: ShapeConfig, with_opt: bool):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_params(cfg, k), key)
    opt = jax.eval_shape(init_opt_state, params) if with_opt else None
    return params, opt
