"""The plain float32 reference agrees with the program at smoke size:
``ServeEngine``'s bucketed prefill and its paged decode steps produce, at
every served position, the logits the reference's full forward pass gives
for the same tokens, up to the bfloat16 rounding the configurations state;
and the greedy tokens served are the reference's.  One case per
configuration: the dense qwen3 block and the mixtral expert block."""
import numpy as np
import pytest

from bench import check, harness, spec, weights
from conftest import smoke_cell

# Program logits against the reference, max|diff| / max|reference| over a
# row.  Two smoke layers in bfloat16 (8 bits of mantissa, about 4e-3 a
# rounding) with float32 accumulation land at about 1e-2.
ROW_TOL = 4e-2
CELLS = ["qwen3-1.7b.chat", "mixtral-8x7b.gen-batch"]


def ref_logits(params, conf, tokens, precision="f32"):
    """The configuration's reference: (S, vocab) logits of ``tokens``."""
    model = spec.model_module(conf)
    return model.forward(params, np.asarray(tokens, np.int32),
                         model.model_keys(conf), precision)[0]


def _serve(name):
    from repro.serve.engine import Request, ServeEngine
    _, cs = smoke_cell(name)
    params = weights.make_weights(cs.config, 5)
    engine = ServeEngine(harness.model_config(cs.config), params,
                         batch_slots=2, max_len=64)
    rows = []                     # (slot, position, logits row)
    prefill, decode = engine._bucket_prefill, engine._decode

    def on_prefill(*a, **kw):
        out = prefill(*a, **kw)
        for slot, n in enumerate(np.asarray(kw["lens"])):
            rows.append((slot, int(n) - 1, np.asarray(out[0][slot])))
        return out

    def on_decode(*a, **kw):
        out = decode(*a, **kw)
        for slot, pos in enumerate(np.asarray(kw["pos"])):
            rows.append((slot, int(pos), np.asarray(out[0][slot])))
        return out

    engine._bucket_prefill, engine._decode = on_prefill, on_decode
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(2, cs.config["vocab_size"], n)
                    .astype(np.int32), max_new_tokens=8)
            for i, n in enumerate((9, 21))]
    engine.run(reqs)
    return cs.config, params, reqs, rows


@pytest.mark.parametrize("name", CELLS)
def test_prefill_then_paged_decode_agree_with_reference(name):
    conf, params, reqs, rows = _serve(name)
    vocab = conf["vocab_size"]
    compared = 0
    for slot, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, r.out_tokens[:-1]])
        full = np.asarray(ref_logits(params, conf, seq))
        end = len(seq)
        for s, pos, got in rows:
            if s != slot or pos >= end:
                continue
            want = full[pos]
            err = np.abs(got[:vocab] - want).max() / np.abs(want).max()
            assert err < ROW_TOL, (name, slot, pos, err)
            compared += 1
        gaps = check.gaps(params, conf, r.prompt, r.out_tokens, 64)[0]
        assert gaps.max() < 0.02 * np.abs(full).max(), gaps
    assert compared == sum(len(r.out_tokens) for r in reqs)


@pytest.mark.parametrize("name", CELLS)
def test_fp8_control_departs_from_reference(name):
    _, cs = smoke_cell(name)
    params = weights.make_weights(cs.config, 5)
    seq = np.arange(2, 40, dtype=np.int32)
    f32 = np.asarray(ref_logits(params, cs.config, seq))
    low = np.asarray(ref_logits(params, cs.config, seq, "fp8"))
    assert np.abs(low - f32).max() > 0.05 * np.abs(f32).max()
