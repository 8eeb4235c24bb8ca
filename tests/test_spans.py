"""The serving path's own measurement points: ``serve.*`` profiler spans
from the engine (read back from a CPU profile) and the model's named
scopes in the lowered programs' op metadata."""
import dataclasses
import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import (decode_step, init_params, make_cache,
                                prefill_bucket)
from repro.runtime import spans
from repro.serve.engine import Request, ServeEngine
from repro.serve.kv_pages import PagedKV, _scatter_rows

SPANS = ("serve.step", "serve.admit", "serve.prefill", "serve.insert",
         "serve.decode", "serve.sync", "serve.sample")


def _profiled_run(tmp_path):
    """Three requests through a 2-slot paged engine under the profiler;
    returns the requests and the ``serve.*`` host events as
    (name, start_ns, end_ns, args)."""
    from jax.profiler import ProfileData
    cfg = get_config("qwen3-1.7b-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=10 + i, prompt=rng.integers(2, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=3)
            for i, n in enumerate((5, 7, 6))]
    eng.run([Request(rid=-1, prompt=reqs[0].prompt, max_new_tokens=2)])
    with jax.profiler.trace(str(tmp_path)):
        eng.run(reqs)
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("serve.")]
    return eng, reqs, events


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_spans_carry_their_counts(tmp_path):
    eng, reqs, events = _profiled_run(tmp_path)
    by = {n: [e for e in events if e[0] == n] for n in SPANS}
    assert all(by[n] for n in SPANS), {n: len(v) for n, v in by.items()}
    rids = {r.rid for r in reqs}
    prompt = {r.rid: len(r.prompt) for r in reqs}

    for _, _, _, a in by["serve.prefill"]:
        ids = [int(x) for x in str(a["rids"]).split(";")]
        assert set(ids) <= rids
        assert a["rows"] == eng.b * a["bucket"]
        assert a["tokens"] == sum(prompt[i] for i in ids)
    admitted = [int(x) for _, _, _, a in by["serve.prefill"]
                for x in str(a["rids"]).split(";")]
    assert sorted(admitted) == sorted(rids)
    for _, _, _, a in by["serve.insert"]:
        assert a["len"] == prompt[a["rid"]] and 0 <= a["slot"] < eng.b
    # One sample per emitted token, each tagged with its request.
    sampled = [a["rid"] for _, _, _, a in by["serve.sample"]]
    assert sorted(sampled) == sorted(
        r.rid for r in reqs for _ in r.out_tokens)
    assert all(0 <= a["slot"] < eng.b for _, _, _, a in by["serve.sample"])
    # Every request is greedy: each token is chosen on the host.
    assert {a["on"] for _, _, _, a in by["serve.sample"]} == {"host"}
    for _, _, _, a in by["serve.decode"]:
        assert 1 <= a["active"] <= eng.b
        assert 0 < a["pages_used"] <= eng.alloc.total
    assert {a["queue"] for _, _, _, a in by["serve.step"]} >= {0, 1}
    assert {a["free"] for _, _, _, a in by["serve.admit"]} <= {0, 1, 2}


def test_engine_spans_nest(tmp_path):
    _, _, events = _profiled_run(tmp_path)
    steps = [e for e in events if e[0] == "serve.step"]
    for name in SPANS[1:]:
        for e in (x for x in events if x[0] == name):
            assert any(_inside(e, s) for s in steps), name
    for p in (e for e in events if e[0] == "serve.prefill"):
        admit = [a for a in events if a[0] == "serve.admit" and _inside(p, a)]
        assert admit and any(_inside(a, s) for a in admit for s in steps)


def test_span_arguments_are_formatted_only_under_a_profiler(monkeypatch):
    def refuse(_value):
        raise AssertionError("argument formatted with no profiler on")

    monkeypatch.setattr(spans, "_arg", refuse)
    with spans.span("serve.sample", rid=3, slot=1):
        pass


_COMMON = {"embed", "attn", "qkv", "core", "out", "final_norm", "lm_head",
           "cast"}
_MOE = {"moe", "router", "dispatch", "experts", "combine"}


def _scopes(lowered) -> set[tuple[str, ...]]:
    """Every op's scope path in a lowered program, split at ``/``."""
    text = lowered.as_text(debug_info=True)
    return {tuple(n.split("/")) for n in re.findall(r'loc\("([^"]+)"', text)}


def _lower(cfg, fn_name: str):
    params = init_params(cfg, jax.random.PRNGKey(0))
    if fn_name == "decode_step":
        kv = PagedKV.build(cfg, slots=2, max_len=32, num_pages=9,
                           page_size=8)
        return jax.jit(functools.partial(decode_step, cfg=cfg)).lower(
            params, tokens=jnp.zeros((2, 1), jnp.int32), cache=kv.cache(),
            pos=jnp.zeros((2,), jnp.int32), page_table=jnp.asarray(kv.table))
    return jax.jit(functools.partial(prefill_bucket, cfg=cfg)).lower(
        params, batch={"tokens": jnp.zeros((2, 16), jnp.int32)},
        cache=make_cache(cfg, 2, 16), lens=jnp.asarray([5, 16], jnp.int32))


@pytest.mark.parametrize("fn_name", ["decode_step", "prefill_bucket"])
@pytest.mark.parametrize("arch", ["qwen3-1.7b-smoke", "mixtral-8x7b-smoke"])
def test_named_scopes_in_lowered_programs(arch, fn_name):
    cfg = get_config(arch)
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, moe_dispatch="ragged")
    paths = _scopes(_lower(cfg, fn_name))
    parts = {c for p in paths for c in p}
    block = _MOE if cfg.family == "moe" else {"mlp"}
    assert _COMMON | block <= parts, sorted(_COMMON | block - parts)

    def has(*chain):
        return any(all(c in p for c in chain) for p in paths)

    # Every weight cast sits under its layer's scope.
    assert has("embed", "cast") and has("lm_head", "cast")
    assert has("attn", "qkv", "cast") and has("attn", "out", "cast")
    if cfg.family == "moe":
        assert has("moe", "router", "cast") and has("moe", "experts", "cast")
    else:
        assert has("mlp", "cast")


def test_kv_insert_scope():
    pool = jnp.zeros((2, 4, 8, 2, 16), jnp.bfloat16)
    lowered = jax.jit(_scatter_rows.__wrapped__).lower(
        pool, jnp.ones((2, 5, 2, 16), jnp.float32), jnp.arange(5))
    assert any("kv_insert" in p for p in _scopes(lowered))
