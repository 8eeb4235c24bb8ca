"""Operations and bytes of the served model, from shapes alone.

Two counts, kept with the benchmark so that no change to the program can
move them:

* ``token_flops`` and friends: the model FLOPs one real token needs (the
  per-token arithmetic of ``repro.roofline.perf_model``, copied): every
  projection at 2 FLOPs per multiply-add, attention scores and values over
  the keys the token sees, the output head only where a logit row is read.
  No padding counts.  ``serve_mfu`` divides these by peak FLOP/s.
* ``prefill_gemms`` / ``decode_gemms``: every GEMM one engine call runs, at
  the shapes it is called with (padding rows and the prefill's key blocks
  included, since the kernels do that work), as (flops, bytes).  Bytes are
  each operand read once and the result written once, counted at two bytes
  an element (bfloat16), the least any implementation moves; routed expert
  GEMMs read each expert's panels once, and at most as many experts as
  there are routed rows.  ``gemm_roofline`` divides the least time these
  take on the chip by the device time of the GEMM events.
"""
from __future__ import annotations

ELT = 2                 # bytes per element moved: bfloat16
PREFILL_KV_BLOCK = 1024  # the blockwise attention's key block


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


def _gemm(m: float, k: float, n: float, *, b_bytes: float | None = None,
          batch: float = 1) -> tuple[float, float]:
    """(flops, bytes) of ``batch`` products (m, k) x (k, n)."""
    b = batch * k * n * ELT if b_bytes is None else b_bytes
    return 2 * batch * m * k * n, batch * (m * k + m * n) * ELT + b


def _layer_gemms(cfg: dict, rows: int) -> list[tuple[float, float]]:
    d, f, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    h, kvh = cfg["num_heads"], cfg["num_kv_heads"]
    out = [_gemm(rows, d, h * hd), _gemm(rows, d, kvh * hd),
           _gemm(rows, d, kvh * hd), _gemm(rows, h * hd, d)]
    e = cfg.get("num_experts", 0)
    if e:
        routed = rows * cfg["top_k"]
        held = min(e, routed)
        out.append(_gemm(rows, d, e))
        out.append(_gemm(routed, d, 2 * f, b_bytes=held * 2 * d * f * ELT))
        out.append(_gemm(routed, f, d, b_bytes=held * f * d * ELT))
    else:
        out.append(_gemm(rows, d, 2 * f))       # gate and up in one launch
        out.append(_gemm(rows, f, d))
    return out


def _attn_gemms(cfg: dict, batch: int, q_rows: int, keys: int):
    """Scores and values of ``batch`` sequences, ``q_rows`` queries each,
    over ``keys`` keys: per (sequence, kv head) one product each way."""
    hd, kvh = cfg["head_dim"], cfg["num_kv_heads"]
    g = cfg["num_heads"] // kvh
    bh = batch * kvh
    return [_gemm(q_rows * g, hd, keys, batch=bh),
            _gemm(q_rows * g, keys, hd, batch=bh)]


def prefill_gemms(cfg: dict, slots: int, bucket: int):
    """GEMMs of one bucketed prefill: ``slots`` rows of ``bucket`` tokens."""
    keys = -(-bucket // PREFILL_KV_BLOCK) * PREFILL_KV_BLOCK
    per_layer = (_layer_gemms(cfg, slots * bucket)
                 + _attn_gemms(cfg, slots, bucket, keys))
    head = _gemm(slots, cfg["d_model"], cfg["vocab_size"])
    return per_layer * cfg["num_layers"] + [head]


def decode_gemms(cfg: dict, slots: int, view_len: int):
    """GEMMs of one decode tick of ``slots`` rows over a paged view of
    ``view_len`` keys per slot."""
    per_layer = _layer_gemms(cfg, slots) + _attn_gemms(cfg, slots, 1,
                                                       view_len)
    head = _gemm(slots, cfg["d_model"], cfg["vocab_size"])
    return per_layer * cfg["num_layers"] + [head]


def roofline_seconds(gemms, peak_flops: float, peak_bw: float) -> float:
    """Least time the chip needs for ``gemms``: per call the larger of its
    FLOPs over peak FLOP/s and its bytes over peak bandwidth."""
    return sum(max(fl / peak_flops, by / peak_bw) for fl, by in gemms)
