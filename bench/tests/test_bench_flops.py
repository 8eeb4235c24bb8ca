"""The yardstick's arithmetic, checked against counts worked out by hand:
the shared roofline and the decoder module's counts."""
from bench import flops, spec

QWEN = spec.load_json(spec.REPO_DIR / "bench/configs/qwen3-1.7b.json")
MIXTRAL = spec.load_json(spec.REPO_DIR / "bench/configs/mixtral-8x7b.json")
DECODER = spec.model_module(QWEN)


def test_dense_token_is_two_flops_per_weight():
    d, f, h, kvh, hd, n_l = 2048, 6144, 16, 8, 128, 28
    weights = n_l * (d * (h + 2 * kvh) * hd + h * hd * d + 3 * d * f)
    # 1.41 B weights outside the embedding: qwen3-1.7b's 1.72 B minus its
    # 0.31 B tied embedding.
    assert abs(weights / 1e9 - 1.41) < 0.01
    one_key = 4 * n_l * h * hd
    assert DECODER.token_flops(QWEN, 1) == 2 * weights + one_key
    assert DECODER.decode_model_flops(QWEN, 0) == (
        2 * weights + one_key + 2 * d * 151936)
    assert DECODER.prefill_model_flops(QWEN, 1) == DECODER.decode_model_flops(
        QWEN, 0)


def test_prefill_attends_causally():
    p = 100
    keys = p * (p + 1) / 2
    dense = DECODER.prefill_model_flops(QWEN, p) - DECODER.head_flops(QWEN)
    attn = 4 * 28 * 16 * 128 * keys
    assert dense - attn == p * (DECODER.token_flops(QWEN, 0))


def test_moe_token_uses_top_k_experts():
    d, f = 4096, 14336
    per_expert = 3 * d * f
    t = DECODER.token_flops(MIXTRAL, 0)
    attn_proj = d * (32 + 16) * 128 + 32 * 128 * d
    assert t == 2 * (attn_proj + 2 * per_expert + d * 8)


def test_decode_gemm_bytes_read_each_weight_and_key_once():
    calls = DECODER.decode_gemms(QWEN, 8, 1024)
    by = sum(b for _, b in calls)
    weights = 28 * (2048 * 32 * 128 + 16 * 128 * 2048 + 3 * 2048 * 6144)
    weights += 2048 * 151936
    keys_values = 28 * 8 * 1024 * 8 * 128 * 2
    least = 2 * (weights + keys_values)
    assert least < by < least * 1.02


def test_routed_gemms_read_at_most_one_panel_per_row():
    one = DECODER._layer_gemms(MIXTRAL, 1)
    full = DECODER._layer_gemms(MIXTRAL, 32)
    panel = 3 * 4096 * 14336 * 2
    assert sum(b for _, b in one[-2:]) < 2 * panel * 1.01
    assert sum(b for _, b in full[-2:]) > 8 * panel


def test_roofline_takes_the_larger_bound():
    assert flops.roofline_seconds([(197e12, 1.0)], 197e12, 819e9) == 1.0
    assert flops.roofline_seconds([(1.0, 819e9)], 197e12, 819e9) == 1.0
