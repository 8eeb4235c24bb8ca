"""The control reads worse than the program and fails the check.  At smoke
size, the same sample of served requests read through the reference in
float8 (one step below the bfloat16 the configurations state) puts first
tokens that lie further below the reference's best, and more of them, than
the program serves, and the same limits that pass the program fail it.
The readings at the cells' own sizes come from ``bench/control.py`` on the
chip and set each cell's limit."""
import pytest

from test_bench_faults import LIMITS, SMOKE_LIMIT


@pytest.mark.parametrize("cell", ["qwen3-1.7b.chat",
                                  "mixtral-8x7b.gen-batch"])
def test_control_fails_and_program_passes(measure_smoke, cell):
    r = measure_smoke(cell, seconds=3.0, limits=LIMITS[cell])
    program, control = r["window"], r["control"]
    assert r["correct"] and not control["correct"], (r["checks"], control)
    assert control["max_logit_gap"] > max(program["max_logit_gap"],
                                          SMOKE_LIMIT)
    assert control["tokens_off_share"] > program["tokens_off_share"]
