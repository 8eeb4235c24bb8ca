"""The correctness check catches a broken serving path.  Each case drives a
whole run at smoke size (``harness.measure``, past ``run.py``'s look for a
chip) with the timed path broken underneath, and sees ``correct`` come out
false; the unbroken run passes under the same limit."""
import pytest

from bench.faults import FAULTS

# Smoke-size limits.  At these widths the reference's logits spread with a
# standard deviation of about 0.22, a wrong token lies a good part of that
# below the best, and the sound program serves the reference's greedy
# token at every position (gap 0, no token off).  A fault that alters one
# token in five moves some of the small sample's share off by less than
# the mixtral cell's own limit, so the smoke runs hold it to its own.
SMOKE_LIMIT = 0.01
LIMITS = {"qwen3-1.7b.chat": {"max_logit_gap": SMOKE_LIMIT},
          "mixtral-8x7b.gen-batch": {"tokens_off_share": 0.05}}


@pytest.mark.parametrize("cell", ["qwen3-1.7b.chat",
                                  "mixtral-8x7b.gen-batch"])
def test_sound_run_is_correct(measure_smoke, cell):
    r = measure_smoke(cell, limits=LIMITS[cell])
    assert r["correct"], r["checks"]
    assert r["window"]["tokens_compared"] >= 40


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["qwen3-1.7b.chat",
                                  "mixtral-8x7b.gen-batch"])
def test_fault_is_caught(measure_smoke, monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch.setattr)
    r = measure_smoke(cell, limits=LIMITS[cell])
    assert not r["correct"], r["checks"]
