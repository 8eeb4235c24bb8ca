"""Fixtures of the benchmark's own tests.  They run on the CPU and never
load the TPU's library: the harness is driven at smoke sizes through
``harness.measure`` with a stand-in device, which skips ``run.py``'s look
for a chip."""
import os
import sys
import time
import types

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(_REPO, "src"), _REPO):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("REPRO_GEMM_BACKEND", "xla")

# Widths of the smoke-size stand-in of each configuration.
SMOKE = {"num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 32, "d_ff": 256, "vocab_size": 512}


def smoke_cell(name: str, *, rate: float = 8.0):
    """The cell ``name`` of BENCHMARK.json at smoke size: smoke widths,
    4 slots, short prompts and outputs."""
    from bench import spec
    bench = spec.benchmark()
    cs = spec.resolve(bench, name)
    conf = dict(cs.config, **SMOKE)
    conf["num_layers"] = min(cs.config["num_layers"], SMOKE["num_layers"])
    if conf.get("num_experts"):
        conf["num_experts"] = 4
    mix = dict(cs.traffic, requests=40,
               prompt_len={"median": 20, "sigma": 0.5, "min": 4, "max": 40},
               output_len={"median": 6, "sigma": 0.5, "min": 2, "max": 12})
    if mix["arrival"] == "poisson":
        mix["rate_per_s"] = rate
    eng = dict(cs.engine, slots=4, max_len=64, check_tokens=40,
               check_requests=4)
    return bench, spec.CellSpec(name, cs.entry, conf, mix, eng)


STAND_IN = types.SimpleNamespace(platform="cpu", device_kind="TPU v5 lite",
                                 memory_stats=lambda: {})


@pytest.fixture
def measure_smoke(monkeypatch):
    """Run ``harness.measure`` on a smoke cell; the persistent compilation
    cache stays as the test session had it."""
    import jax

    from repro.runtime import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    before = jax.config.jax_persistent_cache_min_compile_time_secs

    def run(name, *, seed=3, seconds=2.0, traced=False, limits=None,
            config=None):
        """One smoke run of ``name``, compared under ``limits`` (default:
        the cell's own), its configuration updated by ``config``; the
        control is read too."""
        from bench import harness
        bench, cs = smoke_cell(name)
        if limits is not None:
            cs.engine["limits"] = limits
        cs.config.update(config or {})
        return harness.measure(cs, bench, seed=seed, seconds=seconds,
                               traced=traced, t_process=time.perf_counter(),
                               devices=[STAND_IN], control=True)

    yield run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)
