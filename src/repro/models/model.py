"""Top-level model API: init / train forward / prefill / decode for every
assigned architecture family.

Batch dict convention (see ``launch.dryrun.input_specs`` for the abstract
stand-ins):
    tokens:       (B, S) int32 — always present
    labels:       (B, S) int32 — training only
    loss_mask:    (B, S) f32   — training only (masks pad / patch positions)
    frames:       (B, S_enc, D) — encdec stub frontend (precomputed audio
                  frame embeddings; the conv frontend is OUT of scope)
    patch_embeds: (B, P, D)     — vlm stub frontend (precomputed patch
                  embeddings from the anyres tiler; OUT of scope)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.dist import shard_act
from .layers import dense, embed, rms_norm, unembed
from .transformer import (init_cache, init_lm_params, stack_cached,
                          stack_train, layer_windows, dense_block)
from .attention import attention

__all__ = ["init_params", "forward_train", "loss_fn", "prefill",
           "prefill_bucket", "decode_step", "make_cache", "encode"]


def init_params(cfg: ModelConfig, key) -> dict:
    return init_lm_params(cfg, key)


def _embed_inputs(params, cfg: ModelConfig, batch) -> tuple[jax.Array, jax.Array]:
    """Token (+frontend) embeddings and positions. Returns (h, positions)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("embed"):
        h = embed(batch["tokens"], params["embed"], cdt)
        if cfg.num_patches and "patch_embeds" in batch:
            patches = dense(batch["patch_embeds"].astype(cdt),
                            params["patch_proj"], cdt)
            h = jnp.concatenate([patches, h], axis=1)
    h = shard_act(h, "dp", None, None)
    positions = jnp.arange(h.shape[1])
    return h, positions


def _final_norm(h: jax.Array, params) -> jax.Array:
    with jax.named_scope("final_norm"):
        return rms_norm(h, params["final_norm"])


def _lm_head(h: jax.Array, params, cfg: ModelConfig) -> jax.Array:
    """Logits over the tied embedding table."""
    with jax.named_scope("lm_head"):
        return unembed(h, params["embed"], cfg.vocab_size,
                       jnp.dtype(cfg.compute_dtype))


def encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Encoder stack over precomputed frame embeddings (whisper stub)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    h = dense(frames.astype(cdt), params["frame_proj"], cdt)
    positions = jnp.arange(h.shape[1])
    windows = layer_windows(cfg)

    def body(hh, xs):
        p, w = xs
        hh, _, _ = dense_block(hh, p, cfg, positions=positions, window=w,
                               causal=False)
        return hh, None

    h, _ = jax.lax.scan(body, h, (params["encoder"],
                                  windows[:cfg.encoder_layers]),
                        unroll=True if cfg.scan_unroll else 1)
    return rms_norm(h, params["enc_norm"])


def _cross_kv_stack(params, cfg: ModelConfig, enc_out: jax.Array):
    """Per-decoder-layer cross K/V from encoder output (computed once)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    b, s, _ = enc_out.shape

    def per_layer(p):
        k = dense(enc_out, p["cross"]["wk"], cdt).reshape(
            b, s, cfg.num_kv_heads, cfg.head_dim_)
        v = dense(enc_out, p["cross"]["wv"], cdt).reshape(
            b, s, cfg.num_kv_heads, cfg.head_dim_)
        return k, v

    return jax.vmap(per_layer)(params["layers"])


def forward_train(params, cfg: ModelConfig, batch) -> tuple[jax.Array, jax.Array]:
    """Full-sequence logits for training. Returns (logits, aux_loss)."""
    h, positions = _embed_inputs(params, cfg, batch)
    if cfg.family == "encdec":
        enc_out = encode(params, cfg, batch["frames"])
        cross = _cross_kv_stack(params, cfg, enc_out)
        h, aux = stack_train(params, cfg, h, positions, cross_kv_stack=cross)
    else:
        h, aux = stack_train(params, cfg, h, positions)
    h = _final_norm(h, params)
    if cfg.num_patches:
        h = h[:, cfg.num_patches:]        # logits over text positions only
    logits = _lm_head(h, params, cfg)
    logits = shard_act(logits, "dp", None, "model")
    return logits, aux


def loss_fn(params, cfg: ModelConfig, batch,
            aux_weight: float = 0.01) -> tuple[jax.Array, dict]:
    logits, aux = forward_train(params, cfg, batch)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    # Fused CE over the (vocab-sharded) logits: logsumexp + masked pick, no
    # gather / log_softmax materialization — keeps the vocab dim sharded over
    # the model axis end-to-end (a take_along_axis here would force an
    # all-gather of (B, S, V) f32 on every chip).
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    picked = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                     axis=-1)
    nll = lse - picked
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum(nll * mask) / denom
    total = ce + aux_weight * aux
    return total, {"loss": ce, "aux_loss": aux,
                   "tokens": jnp.sum(mask)}


def make_cache(cfg: ModelConfig, batch_size: int, max_len: int) -> dict:
    """KV/SSM cache sized for ``max_len`` positions (VLM: includes patches)."""
    extra = cfg.num_patches or 0
    return init_cache(cfg, batch_size, max_len + extra)


def prefill(params, cfg: ModelConfig, batch, cache) -> tuple[jax.Array, dict]:
    """Run the prompt through the stack, filling the cache.
    Returns (last-position logits, cache)."""
    h, positions = _embed_inputs(params, cfg, batch)
    if cfg.family == "encdec":
        enc_out = encode(params, cfg, batch["frames"])
        ck, cv = _cross_kv_stack(params, cfg, enc_out)
        cache = dict(cache)
        cache.update({"cross_k": ck, "cross_v": cv})
    h, new_cache, _ = stack_cached(params, cfg, h, positions, cache,
                                   cache_index=jnp.int32(0))
    h = _final_norm(h[:, -1:], params)
    return _lm_head(h, params, cfg)[:, 0], new_cache


def prefill_bucket(params, cfg: ModelConfig, batch, cache,
                   lens: jax.Array) -> tuple[jax.Array, dict]:
    """Length-bucketed batch prefill: the whole bucket of right-padded
    prompts runs through ONE compiled stack pass into a bucket-sized
    contiguous cache, and each row's logits are taken at ITS last valid
    position (``lens`` (B,) = true prompt lengths, tokens padded to the
    bucket on the right).  Causality makes this exact: K/V at position i
    depend only on token i, and row r's logits at lens[r]-1 attend only to
    positions <= lens[r]-1 — pad tokens never influence a valid row.
    Returns ((B, V) logits, cache).  Attention-cache families only (SSM
    state is recurrent — pad tokens would contaminate it)."""
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"bucketed prefill unsupported for {cfg.family}")
    h, positions = _embed_inputs(params, cfg, batch)
    h, new_cache, _ = stack_cached(params, cfg, h, positions, cache,
                                   cache_index=jnp.int32(0))
    extra = cfg.num_patches or 0
    idx = jnp.asarray(lens, jnp.int32) - 1 + extra       # (B,)
    last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    last = _final_norm(last, params)
    return _lm_head(last, params, cfg)[:, 0], new_cache


def decode_step(params, cfg: ModelConfig, tokens: jax.Array, cache: dict,
                pos: jax.Array, page_table: jax.Array | None = None,
                ) -> tuple[jax.Array, dict]:
    """One-token decode. tokens: (B, 1) int32; pos: scalar int32 = number of
    positions already in the cache (VLM: including patches), or a (B,)
    vector of PER-SLOT depths — continuous batching serves slots at mixed
    lengths in one fused step, each writing/masking at its own position.
    ``page_table`` (B, max_pages): ``cache`` holds paged KV pools shared by
    every slot (see ``serve.kv_pages``) instead of per-slot dense buffers.
    Returns (logits (B, V), new cache)."""
    with jax.named_scope("embed"):
        h = embed(tokens, params["embed"], jnp.dtype(cfg.compute_dtype))
    pos = jnp.asarray(pos)
    positions = pos[:, None] if pos.ndim else pos + jnp.arange(1)
    h, new_cache, _ = stack_cached(params, cfg, h, positions, cache,
                                   cache_index=pos, page_table=page_table)
    h = _final_norm(h, params)
    return _lm_head(h, params, cfg)[:, 0], new_cache
