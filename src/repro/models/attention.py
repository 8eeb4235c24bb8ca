"""Attention: GQA with qk-norm, RoPE, sliding-window / chunked / global masks,
blockwise (memory-efficient) computation, and KV-cache decode.

The KV-block scan keeps prefill memory sub-quadratic (required for the 32k
prefill cells) and keeps the HLO small under scan-over-layers.  Per-layer
attention patterns are encoded in one traced scalar ``window`` so a single
scanned stack serves gemma3's 5:1 local:global, mixtral's SWA and llama4's
chunked layers:

    window > 0  : sliding window of that size (SWA)
    window == 0 : global attention
    window < 0  : chunked/local attention with chunk size |window| (iRoPE)

Decode attention over a long KV cache is the paper's T2 GEMM
(K = cache_len >> M = batch, N = head_dim); its cross-chip K-parallel
treatment (flash-decoding) lives in ``repro.serve.decode``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..core.compat import shard_map
from ..core.dist import current_dist
from ..core.gemm import batched_matmul
from .layers import dense, rms_norm, rope

NEG_INF = -1e30


def _bmm_qk(qg: jax.Array, k_blk: jax.Array) -> jax.Array:
    """(B, Sq, KVH, G, D) x (B, Skv, KVH, D) -> (B, Sq, KVH, G, Skv) scores.

    The attention score BMM flattened into the planner's batched GEMM: the
    (batch, kv-head) dims fold into the batch grid dim and the (query, group)
    dims into M, so each entry is the paper's "nt" GEMM with N = kv-block and
    K = head_dim <= 128 — irregular by the §III-A taxonomy, and previously a
    raw einsum the tuner never saw."""
    b, sq, kvh, g, d = qg.shape
    skv = k_blk.shape[1]
    qf = qg.transpose(0, 2, 1, 3, 4).reshape(b * kvh, sq * g, d)
    kf = k_blk.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        b * kvh, skv, d)
    s = batched_matmul(qf, kf, trans="nt", out_dtype=jnp.float32)
    return s.reshape(b, kvh, sq, g, skv).transpose(0, 2, 1, 3, 4)


def _bmm_pv(p: jax.Array, v_blk: jax.Array) -> jax.Array:
    """(B, Sq, KVH, G, Skv) x (B, Skv, KVH, D) -> (B, Sq, KVH, G, D)."""
    b, sq, kvh, g, skv = p.shape
    d = v_blk.shape[-1]
    pf = p.transpose(0, 2, 1, 3, 4).reshape(b * kvh, sq * g, skv)
    vf = v_blk.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        b * kvh, skv, d)
    o = batched_matmul(pf, vf, trans="nn", out_dtype=jnp.float32)
    return o.reshape(b, kvh, sq, g, d).transpose(0, 2, 1, 3, 4)


def init_attention_params(key, d_model: int, num_heads: int,
                          num_kv_heads: int, head_dim: int,
                          qk_norm: bool = False, cross: bool = False,
                          dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    scale = (2.0 / d_model) ** 0.5
    p = {
        "wq": jax.random.normal(ks[0], (d_model, num_heads * head_dim), dtype) * scale,
        "wk": jax.random.normal(ks[1], (d_model, num_kv_heads * head_dim), dtype) * scale,
        "wv": jax.random.normal(ks[2], (d_model, num_kv_heads * head_dim), dtype) * scale,
        "wo": jax.random.normal(ks[3], (num_heads * head_dim, d_model), dtype)
              * (2.0 / (num_heads * head_dim)) ** 0.5,
    }
    if qk_norm:
        p["q_norm"] = jnp.zeros((head_dim,), dtype)
        p["k_norm"] = jnp.zeros((head_dim,), dtype)
    return p


def _mask(q_pos: jax.Array, kv_pos: jax.Array, window: jax.Array,
          causal: bool) -> jax.Array:
    """(Sq, Skv) boolean mask from positions and the window encoding."""
    q = q_pos[:, None].astype(jnp.int32)
    k = kv_pos[None, :].astype(jnp.int32)
    ok = jnp.ones(q.shape[:1] + k.shape[1:], dtype=bool)
    if causal:
        ok = k <= q
    w = jnp.asarray(window, jnp.int32)
    aw = jnp.maximum(jnp.abs(w), 1)
    sliding_ok = jnp.where(w > 0, k > q - aw, True)
    chunk_ok = jnp.where(w < 0, (q // aw) == (k // aw), True)
    return ok & sliding_ok & chunk_ok


def blockwise_attention(
    q: jax.Array,             # (B, Sq, H, D)
    k: jax.Array,             # (B, Skv, KVH, D)
    v: jax.Array,             # (B, Skv, KVH, D)
    *,
    q_positions: jax.Array,   # (Sq,)
    kv_positions: jax.Array,  # (Skv,)
    window: jax.Array | int = 0,
    causal: bool = True,
    kv_valid_len: jax.Array | None = None,
    block_kv: int = 1024,
    unroll: bool = False,
) -> jax.Array:
    """Memory-efficient attention with running-max/denominator over KV blocks."""
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, d).astype(jnp.float32)
    scale = d ** -0.5

    pad = (-skv) % block_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad),
                               constant_values=jnp.iinfo(jnp.int32).max // 2)
    nb = k.shape[1] // block_kv
    kb = k.reshape(b, nb, block_kv, kvh, d).swapaxes(0, 1)
    vb = v.reshape(b, nb, block_kv, kvh, d).swapaxes(0, 1)
    pb = kv_positions.reshape(nb, block_kv)
    valid = kv_valid_len if kv_valid_len is not None else skv

    def step(carry, xs):
        acc, m, l = carry
        k_blk, v_blk, pos_blk = xs
        s = _bmm_qk(qg, k_blk) * scale
        msk = _mask(q_positions, pos_blk, window, causal)
        msk = msk & (pos_blk < valid)[None, :]
        s = jnp.where(msk[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + _bmm_pv(p, v_blk)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((b, sq, kvh, g, d), jnp.float32)
    m0 = jnp.full((b, sq, kvh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kvh, g), jnp.float32)
    # Flash-attention-style backward: recompute per-block scores/probs from
    # q/k instead of saving (nb, B, Sq, H, block) residuals across steps.
    (acc, _, l), _ = jax.lax.scan(jax.checkpoint(step), (acc0, m0, l0),
                                  (kb, vb, pb), unroll=True if unroll else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, sq, h, d).astype(q.dtype)


def decode_attention(q, ck, cv, *, q_pos, window):
    """Single-token decode over the full cache with PER-ROW positions.

    ``q`` (B, 1, H, D); ``ck``/``cv`` (B, S, KVH, D); ``q_pos`` (B,) — the
    cache row each batch entry just wrote.  ``_mask`` broadcasts the (B,)
    query positions against the (S,) cache positions into a (B, S) per-row
    mask, so slots at different depths coexist in one fused decode batch:
    row b attends exactly k <= q_pos[b] under its own window, and rows
    beyond its depth (zeros, or a previous occupant's remnants) are
    excluded instead of inflating the softmax denominator."""
    b, sq, h, d = q.shape
    kvh = ck.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, d).astype(jnp.float32)
    s_ = _bmm_qk(qg, ck) * (d ** -0.5)           # (B, 1, KVH, G, Skv)
    kv_pos = jnp.arange(ck.shape[1])
    msk = _mask(q_pos, kv_pos, window, causal=True)       # (B, Skv)
    s_ = jnp.where(msk[:, None, None, None, :], s_, NEG_INF)
    m = jnp.max(s_, axis=-1, keepdims=True)
    p = jnp.exp(s_ - m)
    out = _bmm_pv(p, cv) / jnp.maximum(jnp.sum(p, axis=-1)[..., None],
                                       1e-30)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def flash_decode(
    q: jax.Array,                # (B, 1, H, D) — replicated over model axis
    ck: jax.Array,               # (B, S, KVH, D) — S sharded over model axis
    cv: jax.Array,
    *,
    pos: jax.Array,              # scalar: index of the newest valid token
    window: jax.Array | int,
    dist,
) -> jax.Array:
    """Sequence-parallel decode attention — the paper's K-parallel strategy
    (Alg. 5) at cluster scale, a.k.a. flash-decoding.

    The KV cache's sequence dim is sharded over the model axis; each chip
    computes a partial softmax-attention (acc, running max, denominator)
    over its K-chunk, and partials are reduced over ICI with a log-sum-exp
    correction — the GSM reduction of the paper with the numerically-safe
    merge softmax needs.  The decode GEMMs q@K^T / p@V are T2-shaped
    (K = cache_len >> M = batch, N = head_dim).
    """
    b, _, h, d = q.shape
    _, s, kvh, _ = ck.shape
    axis = dist.model_axis
    dp = dist.dp_axes
    bshard = dp if (b % dist.dp_size == 0 and b >= dist.dp_size) else None
    g = h // kvh
    scale = d ** -0.5

    def kernel(q_l, k_l, v_l):
        s_loc = k_l.shape[1]
        shard = jax.lax.axis_index(axis)
        kv_pos = shard * s_loc + jnp.arange(s_loc)
        bl = q_l.shape[0]
        qg = q_l[:, 0].reshape(bl, kvh, g, d).astype(jnp.float32)
        # The decode score/value BMMs are T2-shaped per (batch, kv-head)
        # entry (K = cache shard >> M = q-group); flatten them into the
        # planner's batched GEMM like the prefill path does.
        kf = k_l.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
            bl * kvh, s_loc, d)
        s_ = batched_matmul(qg.reshape(bl * kvh, g, d), kf, trans="nt",
                            out_dtype=jnp.float32
                            ).reshape(bl, kvh, g, s_loc) * scale
        msk = _mask(pos[None], kv_pos, window, causal=True)[0]
        msk = msk & (kv_pos <= pos)
        s_ = jnp.where(msk[None, None, None, :], s_, NEG_INF)
        m = jnp.max(s_, axis=-1)
        p = jnp.exp(s_ - m[..., None])
        l = jnp.sum(p, axis=-1)
        vf = v_l.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
            bl * kvh, s_loc, d)
        acc = batched_matmul(p.reshape(bl * kvh, g, s_loc), vf, trans="nn",
                             out_dtype=jnp.float32).reshape(bl, kvh, g, d)
        # LSE-corrected reduction over the model axis (paper Alg. 5 line 12).
        gm = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - gm)
        l_g = jax.lax.psum(l * corr, axis)
        acc_g = jax.lax.psum(acc * corr[..., None], axis)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.reshape(-1, 1, h, d).astype(q_l.dtype)

    fn = shard_map(
        kernel, mesh=dist.mesh,
        in_specs=(P(bshard, None, None, None),
                  P(bshard, axis, None, None),
                  P(bshard, axis, None, None)),
        out_specs=P(bshard, None, None, None),
    )
    return fn(q, ck, cv)


def attention(
    x: jax.Array,                  # (B, S, D_model)
    params: dict,
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    positions: jax.Array,          # (S,)
    window: jax.Array | int = 0,
    causal: bool = True,
    qk_norm: bool = False,
    rope_theta: float = 10000.0,
    use_rope: bool = True,
    kv_cache: tuple[jax.Array, jax.Array] | None = None,
    cache_index: jax.Array | None = None,
    cross_kv: tuple[jax.Array, jax.Array] | None = None,
    compute_dtype=jnp.bfloat16,
    block_kv: int = 1024,
    unroll: bool = False,
    residual: jax.Array | None = None,
    page_table: jax.Array | None = None,
):
    """Full attention layer. Returns (out, new_kv_cache | None).

    * training/prefill: kv from x, optionally written into a fresh cache.
    * decode: ``kv_cache`` given + ``cache_index`` = current position; the
      new token's K/V are inserted and attention runs over the whole buffer.
      ``cache_index`` may be a (B,) vector (continuous batching at mixed
      depths): each row writes its own cache row and masks under its own
      causal horizon; ``positions`` is then (B, S).
    * cross-attention: ``cross_kv`` precomputed (B, S_enc, KVH, D) pair.
    * ``residual``: the block's residual stream (B, S, D_model), added in
      the out-projection's fused epilogue — the transformer's ``h + attn``
      without a separate elementwise pass over the output.
    * paged decode: ``page_table`` (B, max_pages) given, ``kv_cache`` is the
      PHYSICAL page pool (num_pages, page_size, KVH, D) shared by every
      slot (see ``serve.kv_pages``).  The new token's K/V scatter at the
      slot's physical row (table[b, idx//page] * page + idx%page) and each
      slot's logical view is gathered back out of the pool; the reserved
      null page 0 absorbs inactive slots' writes and is excluded by the
      per-row position masks (positions past a slot's depth never attend).
    """
    b, s, _ = x.shape
    with jax.named_scope("qkv"):
        q = dense(x, params["wq"], compute_dtype).reshape(
            b, s, num_heads, head_dim)
        # (S,) positions broadcast over the batch; (B, S) are per-row
        # (vector cache_index decode) and feed rope directly.
        pos2 = positions if positions.ndim == 2 else positions[None, :]
        k = v = None                       # cross-attention: precomputed
        if cross_kv is None:
            k = dense(x, params["wk"], compute_dtype).reshape(
                b, s, num_kv_heads, head_dim)
            v = dense(x, params["wv"], compute_dtype).reshape(
                b, s, num_kv_heads, head_dim)
        if qk_norm:
            q = rms_norm(q, params["q_norm"])
            if k is not None:
                k = rms_norm(k, params["k_norm"])
        if use_rope:
            q = rope(q, pos2, rope_theta)
            if k is not None:
                k = rope(k, pos2, rope_theta)
    with jax.named_scope("core"):
        out, new_cache = _attend(
            q, k, v, positions=positions, window=window, causal=causal,
            kv_cache=kv_cache, cache_index=cache_index, cross_kv=cross_kv,
            block_kv=block_kv, unroll=unroll, page_table=page_table)
        out = out.reshape(b, s, num_heads * head_dim)
    with jax.named_scope("out"):
        return dense(out, params["wo"], compute_dtype,
                     residual=residual), new_cache


def _attend(q, k, v, *, positions, window, causal, kv_cache, cache_index,
            cross_kv, block_kv, unroll, page_table):
    """The attention core of ``attention`` on projected (and roped) q, k, v:
    cache write, then softmax attention over the keys in reach.  Returns
    (out (B, S, H, D), new_kv_cache | None)."""
    b, s, _, head_dim = q.shape
    if cross_kv is not None:
        k, v = cross_kv
        kv_pos = jnp.arange(k.shape[1])
        out = blockwise_attention(
            q, k, v, q_positions=positions, kv_positions=kv_pos,
            window=0, causal=False, block_kv=block_kv, unroll=unroll)
        return out, None
    num_kv_heads = k.shape[2]
    if kv_cache is not None and page_table is not None:
        # Paged single-token decode: scatter the new K/V at the slot's
        # physical row, gather the logical per-slot view, run the
        # per-row-masked decode attention over it.
        ck, cv = kv_cache                  # (num_pages, page, KVH, D)
        assert cache_index is not None and s == 1
        idx = jnp.asarray(cache_index)
        nump, page = ck.shape[0], ck.shape[1]
        phys = (page_table[jnp.arange(b), idx // page] * page
                + idx % page)
        flat_k = ck.reshape(nump * page, num_kv_heads, head_dim)
        flat_v = cv.reshape(nump * page, num_kv_heads, head_dim)
        flat_k = flat_k.at[phys].set(k[:, 0].astype(flat_k.dtype))
        flat_v = flat_v.at[phys].set(v[:, 0].astype(flat_v.dtype))

        def view(flat):
            paged = flat.reshape(nump, page, num_kv_heads, head_dim)
            return paged[page_table].reshape(
                b, -1, num_kv_heads, head_dim)

        out = decode_attention(q, view(flat_k), view(flat_v),
                               q_pos=idx, window=window)
        return out, (flat_k.reshape(ck.shape), flat_v.reshape(cv.shape))
    if kv_cache is None:
        out = blockwise_attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            window=window, causal=causal, block_kv=block_kv, unroll=unroll)
        return out, None
    ck, cv = kv_cache
    assert cache_index is not None
    idx = jnp.asarray(cache_index)
    if idx.ndim:
        # Per-row insert: slot b's token lands at ITS depth idx[b],
        # not at the batch max.
        upd = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i, 0, 0)))
        ck = upd(ck, k.astype(ck.dtype), idx)
        cv = upd(cv, v.astype(cv.dtype), idx)
    else:
        ck = jax.lax.dynamic_update_slice(
            ck, k.astype(ck.dtype), (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cv, v.astype(cv.dtype), (0, cache_index, 0, 0))
    dist = current_dist()
    if s > 1:
        # Prefill from an empty cache: the freshly computed K/V span
        # the whole valid range, so attend over them directly (keeps
        # the scan over KV blocks off the sharded cache buffer).
        out = blockwise_attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            window=window, causal=causal, block_kv=block_kv, unroll=unroll)
    elif idx.ndim:
        # Mixed-depth fused decode: per-row masks from the (B,)
        # positions.
        out = decode_attention(q, ck, cv, q_pos=idx, window=window)
    elif dist is not None and dist.sp_decode and dist.model_size > 1:
        # K-parallel decode across chips (paper Alg. 5).
        out = flash_decode(q, ck, cv, pos=cache_index + s - 1,
                           window=window, dist=dist)
    else:
        kv_pos = jnp.arange(ck.shape[1])
        out = blockwise_attention(
            q, ck, cv, q_positions=positions, kv_positions=kv_pos,
            window=window, causal=causal,
            kv_valid_len=cache_index + s, block_kv=block_kv,
            unroll=unroll)
    return out, (ck, cv)
