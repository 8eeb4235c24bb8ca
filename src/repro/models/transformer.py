"""Composable transformer stacks: dense / MoE / SSM / hybrid / enc-dec / VLM.

All stacks scan over layers (``jax.lax.scan`` with stacked params as xs) so
the lowered HLO stays compact for the 512-device dry-run, and activation
rematerialization policies apply uniformly to the scan body.

Per-layer attention patterns ride along as a scanned int32 array (see
``models.attention`` for the window encoding), which lets gemma3 (5:1
local:global), mixtral (SWA) and llama4 (chunked local 3:1) share one stack.

The hybrid (zamba2) stack is an outer scan over groups of ``attn_every``
Mamba2 layers followed by ONE shared attention+MLP block (single param set
reused at every application — faithful to Zamba2's shared-block design),
plus a trailing remainder scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.dist import shard_act
from .attention import attention, init_attention_params
from .layers import rms_norm, swiglu, he_init
from .moe import init_moe_params, moe_mlp
from .ssm import (CONV_WIDTH, HEADDIM, init_ssm_params, init_ssm_state,
                  ssd_decode_step, ssd_forward, ssm_dims)


def _cdt(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ------------------------------ param init ------------------------------

def init_dense_block(key, cfg: ModelConfig, *, moe: bool = False,
                     cross: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    dt = _pdt(cfg)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dt),
        "attn": init_attention_params(
            ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim_, qk_norm=cfg.qk_norm, dtype=dt),
        "ln2": jnp.zeros((cfg.d_model,), dt),
    }
    if cross:
        p["ln_cross"] = jnp.zeros((cfg.d_model,), dt)
        p["cross"] = init_attention_params(
            ks[1], cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim_, qk_norm=False, dtype=dt)
    if moe:
        p["moe"] = init_moe_params(ks[2], cfg.d_model, cfg.d_ff,
                                   cfg.num_experts, dtype=dt)
    else:
        p["mlp"] = {
            "w_gate": he_init(ks[2], (cfg.d_model, cfg.d_ff), dt),
            "w_up": he_init(jax.random.fold_in(ks[2], 1),
                            (cfg.d_model, cfg.d_ff), dt),
            "w_down": he_init(ks[3], (cfg.d_ff, cfg.d_model), dt,
                              fan_in=cfg.d_ff),
        }
    return p


def init_ssm_block(key, cfg: ModelConfig) -> dict:
    return {
        "ln": jnp.zeros((cfg.d_model,), _pdt(cfg)),
        "ssm": init_ssm_params(key, cfg.d_model, cfg.ssm_state, _pdt(cfg)),
    }


def _init_stack(key, n: int, block_init):
    keys = jax.random.split(key, n)
    return jax.vmap(block_init)(keys)


def init_lm_params(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 8)
    dt = _pdt(cfg)
    params: dict = {
        "embed": jax.random.normal(
            ks[0], (cfg.vocab_padded, cfg.d_model), dt) * 0.02,
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = _init_stack(
            ks[1], cfg.num_layers, lambda k: init_dense_block(k, cfg))
    elif fam == "moe":
        params["layers"] = _init_stack(
            ks[1], cfg.num_layers, lambda k: init_dense_block(k, cfg, moe=True))
    elif fam == "ssm":
        params["layers"] = _init_stack(
            ks[1], cfg.num_layers, lambda k: init_ssm_block(k, cfg))
    elif fam == "hybrid":
        params["layers"] = _init_stack(
            ks[1], cfg.num_layers, lambda k: init_ssm_block(k, cfg))
        params["shared_attn"] = init_dense_block(ks[2], cfg)
    elif fam == "encdec":
        params["encoder"] = _init_stack(
            ks[3], cfg.encoder_layers, lambda k: init_dense_block(k, cfg))
        params["enc_norm"] = jnp.zeros((cfg.d_model,), dt)
        params["layers"] = _init_stack(
            ks[1], cfg.num_layers,
            lambda k: init_dense_block(k, cfg, cross=True))
    else:
        raise ValueError(fam)
    if cfg.num_patches:
        params["patch_proj"] = he_init(ks[4], (cfg.d_model, cfg.d_model), dt)
    if cfg.encoder_seq:
        params["frame_proj"] = he_init(ks[5], (cfg.d_model, cfg.d_model), dt)
    return params


# ------------------------------ block fwd -------------------------------

def _mlp_or_moe(h, p, cfg: ModelConfig):
    with jax.named_scope("moe" if "moe" in p else "mlp"):
        x = _gathered(rms_norm(h, p["ln2"]), cfg)
        if "moe" in p:
            b, s, d = x.shape
            y, aux = moe_mlp(x.reshape(b * s, d), p["moe"],
                             num_experts=cfg.num_experts, top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor,
                             compute_dtype=_cdt(cfg),
                             dispatch=cfg.moe_dispatch,
                             quant=getattr(cfg, "quant", "none"))
            return h + y.reshape(b, s, d), aux
        # Residual add fused into the down projection's epilogue (and the
        # gate/up pair is one fused kernel launch inside swiglu).
        return swiglu(x, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                      p["mlp"]["w_down"], _cdt(cfg),
                      residual=h), jnp.float32(0.0)


def _gathered(x, cfg):
    """Explicit sequence-parallel all-gather point (Megatron-SP style):
    norm inputs are gathered over the model axis, so GSPMD places ONE
    bf16 all-gather here and a reduce-scatter at the block boundary instead
    of improvising f32 gathers + activation-scale all-reduces in backward."""
    from ..core.dist import current_dist
    ctx = current_dist()
    if ctx is not None and ctx.sp_inputs and x.shape[1] > 1:
        x = shard_act(x, "dp", None, None)
    return x


def dense_block(h, p, cfg: ModelConfig, *, positions, window,
                kv=None, cache_index=None, cross_kv=None, causal=True,
                use_rope=True, page_table=None):
    """Returns (h, new_kv, aux).  The residual adds around attention (and
    the MLP, see ``_mlp_or_moe``) ride the out-projections' fused epilogues
    instead of separate elementwise passes over the block output.
    ``page_table`` switches decode to the paged KV pool (serve.kv_pages).
    Named scopes ``attn`` / ``cross_attn`` and ``mlp`` / ``moe`` mark each
    sub-block's ops in the compiled program's metadata."""
    with jax.named_scope("attn"):
        h, new_kv = attention(
            _gathered(rms_norm(h, p["ln1"]), cfg), p["attn"],
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim_, positions=positions, window=window,
            causal=causal, qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
            use_rope=use_rope, kv_cache=kv, cache_index=cache_index,
            compute_dtype=_cdt(cfg), unroll=cfg.scan_unroll, residual=h,
            page_table=page_table)
    if cross_kv is not None:
        with jax.named_scope("cross_attn"):
            h, _ = attention(
                rms_norm(h, p["ln_cross"]), p["cross"],
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim_, positions=positions, window=0,
                causal=False, qk_norm=False, rope_theta=cfg.rope_theta,
                use_rope=False, cross_kv=cross_kv, compute_dtype=_cdt(cfg),
                unroll=cfg.scan_unroll, residual=h)
    h, aux = _mlp_or_moe(h, p, cfg)
    # Sequence parallelism on the residual stream (training): the layer-scan
    # carry is the dominant live activation (L x B x S x D saved for the
    # backward); sharding S over the model axis cuts it by the TP degree.
    # Decode (S == 1) falls back to replicated automatically.
    h = shard_act(h, "dp", "model" if h.shape[1] > 1 else None, None)
    return h, new_kv, aux


def ssm_block(h, p, cfg: ModelConfig, state=None):
    """Returns (h, new_state)."""
    x = rms_norm(h, p["ln"])
    if state is None:
        y, _ = ssd_forward(x, p["ssm"], ssm_state=cfg.ssm_state,
                           chunk=cfg.ssm_chunk, compute_dtype=_cdt(cfg),
                           unroll=cfg.scan_unroll)
        return shard_act(h + y, "dp",
                         "model" if h.shape[1] > 1 else None, None), None
    if x.shape[1] == 1:
        y, new_state = ssd_decode_step(x, p["ssm"], state,
                                       ssm_state=cfg.ssm_state,
                                       compute_dtype=_cdt(cfg))
        return h + y, new_state
    # prefill: chunked scan, return final state (+ fresh conv tail)
    y, h_final = ssd_forward(x, p["ssm"], ssm_state=cfg.ssm_state,
                             chunk=cfg.ssm_chunk, compute_dtype=_cdt(cfg),
                             initial_state=state["h"], unroll=cfg.scan_unroll)
    d_inner, _, n = ssm_dims(cfg.d_model, cfg.ssm_state)
    # conv tail = silu-input window of the last (W-1) positions
    zxbcdt_tail = x[:, -(CONV_WIDTH - 1):]
    # recompute the conv input channels for the tail (cheap: W-1 positions)
    from .layers import dense as _dense
    tail = _dense(zxbcdt_tail, p["ssm"]["in_proj"], _cdt(cfg))
    xbc_tail = jnp.concatenate(
        [tail[..., d_inner:2 * d_inner],
         tail[..., 2 * d_inner:2 * d_inner + 2 * n]], axis=-1)
    return h + y, {"h": h_final, "conv": xbc_tail}


def _unroll(cfg: ModelConfig):
    return True if cfg.scan_unroll else 1


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def layer_windows(cfg: ModelConfig) -> jax.Array:
    return jnp.asarray(cfg.windows(), jnp.int32)


# ------------------------------ stacks ----------------------------------

def stack_train(params, cfg: ModelConfig, h, positions, *,
                cross_kv_stack=None, causal=True, use_rope=True):
    """Scan a dense/moe/ssm/hybrid stack without caches. -> (h, aux)."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm", "encdec"):
        windows = layer_windows(cfg)

        def body(carry, xs):
            hh, aux = carry
            if cross_kv_stack is not None:
                p, w, ckv = xs
            else:
                p, w = xs
                ckv = None
            hh, _, a = dense_block(hh, p, cfg, positions=positions, window=w,
                                   cross_kv=ckv, causal=causal,
                                   use_rope=use_rope)
            return (hh, aux + a), None

        xs = (params["layers"], windows)
        if cross_kv_stack is not None:
            xs = xs + (cross_kv_stack,)
        (h, aux), _ = jax.lax.scan(_remat(body, cfg), (h, jnp.float32(0.0)), xs,
                                   unroll=_unroll(cfg))
        return h, aux

    if fam == "ssm":
        def body(hh, p):
            hh, _ = ssm_block(hh, p, cfg)
            return hh, None
        h, _ = jax.lax.scan(_remat(body, cfg), h, params["layers"],
                            unroll=_unroll(cfg))
        return h, jnp.float32(0.0)

    if fam == "hybrid":
        return _hybrid_train(params, cfg, h, positions)

    raise ValueError(fam)


def _hybrid_split(cfg: ModelConfig, stack):
    e = cfg.attn_every
    g = cfg.num_layers // e
    r = cfg.num_layers - g * e
    grouped = jax.tree.map(
        lambda a: a[:g * e].reshape((g, e) + a.shape[1:]), stack)
    rem = jax.tree.map(lambda a: a[g * e:], stack) if r else None
    return grouped, rem, g, r


def _hybrid_train(params, cfg: ModelConfig, h, positions):
    grouped, rem, g, r = _hybrid_split(cfg, params["layers"])
    shared = params["shared_attn"]

    def inner(hh, p):
        hh, _ = ssm_block(hh, p, cfg)
        return hh, None

    def group_body(hh, p_group):
        hh, _ = jax.lax.scan(inner, hh, p_group, unroll=_unroll(cfg))
        hh, _, _ = dense_block(hh, shared, cfg, positions=positions, window=0)
        return hh, None

    h, _ = jax.lax.scan(_remat(group_body, cfg), h, grouped,
                        unroll=_unroll(cfg))
    if r:
        h, _ = jax.lax.scan(inner, h, rem, unroll=_unroll(cfg))
    return h, jnp.float32(0.0)


def stack_cached(params, cfg: ModelConfig, h, positions, cache, cache_index,
                 *, causal=True, use_rope=True, page_table=None):
    """Scan with KV/SSM caches (prefill & decode). -> (h, new_cache, aux).
    ``page_table`` (B, max_pages): the cache leaves are paged pools shared
    across slots (one table for every layer — it rides the scan closure)."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        windows = layer_windows(cfg)

        def body(carry, xs):
            hh, aux = carry
            p, w, k_l, v_l = xs
            hh, new_kv, a = dense_block(
                hh, p, cfg, positions=positions, window=w,
                kv=(k_l, v_l), cache_index=cache_index, causal=causal,
                use_rope=use_rope, page_table=page_table)
            return (hh, aux + a), new_kv

        (h, aux), (nk, nv) = jax.lax.scan(
            body, (h, jnp.float32(0.0)),
            (params["layers"], windows, cache["k"], cache["v"]),
            unroll=_unroll(cfg))
        return h, {"k": nk, "v": nv}, aux

    if fam == "encdec":
        windows = layer_windows(cfg)

        def body(carry, xs):
            hh, aux = carry
            p, w, k_l, v_l, ck_l, cv_l = xs
            hh, new_kv, a = dense_block(
                hh, p, cfg, positions=positions, window=w,
                kv=(k_l, v_l), cache_index=cache_index,
                cross_kv=(ck_l, cv_l))
            return (hh, aux + a), new_kv

        (h, aux), (nk, nv) = jax.lax.scan(
            body, (h, jnp.float32(0.0)),
            (params["layers"], windows, cache["k"], cache["v"],
             cache["cross_k"], cache["cross_v"]), unroll=_unroll(cfg))
        new_cache = dict(cache)
        new_cache.update({"k": nk, "v": nv})
        return h, new_cache, aux

    if fam == "ssm":
        def body(hh, xs):
            p, st_h, st_conv = xs
            hh, new_state = ssm_block(hh, p, cfg,
                                      state={"h": st_h, "conv": st_conv})
            return hh, (new_state["h"], new_state["conv"])

        h, (nh, nconv) = jax.lax.scan(
            body, h, (params["layers"], cache["h"], cache["conv"]),
            unroll=_unroll(cfg))
        return h, {"h": nh, "conv": nconv}, jnp.float32(0.0)

    if fam == "hybrid":
        return _hybrid_cached(params, cfg, h, positions, cache, cache_index)

    raise ValueError(fam)


def _hybrid_cached(params, cfg: ModelConfig, h, positions, cache, cache_index):
    grouped, rem, g, r = _hybrid_split(cfg, params["layers"])
    shared = params["shared_attn"]
    e = cfg.attn_every

    def split_state(tree, count, width):
        return jax.tree.map(
            lambda a: a[:count * width].reshape((count, width) + a.shape[1:]),
            tree)

    ssm_state = {"h": cache["ssm_h"], "conv": cache["ssm_conv"]}
    grouped_state = split_state(ssm_state, g, e)
    rem_state = jax.tree.map(lambda a: a[g * e:], ssm_state) if r else None

    def inner(hh, xs):
        p, st_h, st_conv = xs
        hh, ns = ssm_block(hh, p, cfg, state={"h": st_h, "conv": st_conv})
        return hh, (ns["h"], ns["conv"])

    def group_body(hh, xs):
        p_group, st_h, st_conv, ak, av = xs
        hh, (nh, nconv) = jax.lax.scan(inner, hh, (p_group, st_h, st_conv),
                                       unroll=_unroll(cfg))
        hh, new_kv, _ = dense_block(hh, shared, cfg, positions=positions,
                                    window=0, kv=(ak, av),
                                    cache_index=cache_index)
        return hh, (nh, nconv, new_kv[0], new_kv[1])

    h, (nh_g, nconv_g, nak, nav) = jax.lax.scan(
        group_body, h,
        (grouped, grouped_state["h"], grouped_state["conv"],
         cache["attn_k"], cache["attn_v"]), unroll=_unroll(cfg))
    nh = nh_g.reshape((g * e,) + nh_g.shape[2:])
    nconv = nconv_g.reshape((g * e,) + nconv_g.shape[2:])
    if r:
        h, (nh_r, nconv_r) = jax.lax.scan(
            inner, h, (rem, rem_state["h"], rem_state["conv"]),
            unroll=_unroll(cfg))
        nh = jnp.concatenate([nh, nh_r], axis=0)
        nconv = jnp.concatenate([nconv, nconv_r], axis=0)
    new_cache = {"ssm_h": nh, "ssm_conv": nconv, "attn_k": nak, "attn_v": nav}
    return h, new_cache, jnp.float32(0.0)


# ------------------------------ caches ----------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> dict:
    dtype = dtype or _cdt(cfg)
    fam = cfg.family
    kvh, hd = cfg.num_kv_heads, cfg.head_dim_
    L = cfg.num_layers
    if fam in ("dense", "moe", "vlm"):
        return {
            "k": jnp.zeros((L, batch, max_len, kvh, hd), dtype),
            "v": jnp.zeros((L, batch, max_len, kvh, hd), dtype),
        }
    if fam == "encdec":
        return {
            "k": jnp.zeros((L, batch, max_len, kvh, hd), dtype),
            "v": jnp.zeros((L, batch, max_len, kvh, hd), dtype),
            "cross_k": jnp.zeros((L, batch, cfg.encoder_seq, kvh, hd), dtype),
            "cross_v": jnp.zeros((L, batch, cfg.encoder_seq, kvh, hd), dtype),
        }
    if fam == "ssm":
        st = init_ssm_state(batch, cfg.d_model, cfg.ssm_state, dtype)
        return {
            "h": jnp.zeros((L,) + st["h"].shape, st["h"].dtype),
            "conv": jnp.zeros((L,) + st["conv"].shape, st["conv"].dtype),
        }
    if fam == "hybrid":
        st = init_ssm_state(batch, cfg.d_model, cfg.ssm_state, dtype)
        g = cfg.num_layers // cfg.attn_every
        return {
            "ssm_h": jnp.zeros((L,) + st["h"].shape, st["h"].dtype),
            "ssm_conv": jnp.zeros((L,) + st["conv"].shape, st["conv"].dtype),
            "attn_k": jnp.zeros((g, batch, max_len, kvh, hd), dtype),
            "attn_v": jnp.zeros((g, batch, max_len, kvh, hd), dtype),
        }
    raise ValueError(fam)
