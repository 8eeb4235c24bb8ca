"""Ahead-of-time compiles of the main-path ftIMM kernels for a described
TPU v5e chip, at real widths: Mosaic and XLA's TPU compiler refuse here
what interpret mode cannot see (VMEM overruns, misaligned block shapes).
Nothing runs; a compile that passes says the kernels build, not what they
compute or how fast.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.gemm import (Epilogue, grouped_swiglu, matmul,
                             ragged_swiglu)
from repro.models.model import (decode_step, init_params, make_cache,
                                prefill_bucket)
from repro.serve.kv_pages import pages_for

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas(monkeypatch):
    """Dispatch to the compiled Pallas kernels (not the CPU's XLA path)."""
    monkeypatch.setenv("REPRO_GEMM_BACKEND", "pallas")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _placed(tree, sharding):
    return jax.tree.map(lambda x: _sds(x.shape, x.dtype, sharding), tree)


def _compile(fn, *args, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _assert_scopes(compiled, *scopes):
    """The compiled program's op metadata names each of ``scopes`` as a
    component of some op's scope path."""
    parts = {c for name in re.findall(r'op_name="([^"]+)"',
                                      compiled.as_text())
             for c in name.split("/")}
    assert set(scopes) <= parts, sorted(set(scopes) - parts)


def _qwen(layers: int = 2):
    return dataclasses.replace(get_config("qwen3-1.7b"), num_layers=layers)


def _params(cfg, sharding):
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return _placed(shapes, sharding)


def test_qwen3_decode_step_paged(one_chip, pallas):
    """qwen3-1.7b decode, published widths, 2 layers, paged KV."""
    cfg = _qwen()
    slots, page, max_len = 8, 16, 512
    max_pages = pages_for(max_len, page)
    pool = (cfg.num_layers, slots * max_pages + 1, page, cfg.num_kv_heads,
            cfg.head_dim_)
    cache = {"k": _sds(pool, BF16, one_chip), "v": _sds(pool, BF16, one_chip)}
    compiled = _compile(functools.partial(decode_step, cfg=cfg),
                        _params(cfg, one_chip),
                        tokens=_sds((slots, 1), jnp.int32, one_chip),
                        cache=cache, pos=_sds((slots,), jnp.int32, one_chip),
                        page_table=_sds((slots, max_pages), jnp.int32,
                                        one_chip))
    _assert_scopes(compiled, "attn", "mlp", "cast", "lm_head")


def test_qwen3_prefill_bucket(one_chip, pallas):
    """qwen3-1.7b bucketed prefill, 4 prompts x 128 tokens (M = 512)."""
    cfg = _qwen()
    b, s = 4, 128
    cache = _placed(jax.eval_shape(lambda: make_cache(cfg, b, s)), one_chip)
    compiled = _compile(functools.partial(prefill_bucket, cfg=cfg),
                        _params(cfg, one_chip),
                        batch={"tokens": _sds((b, s), jnp.int32, one_chip)},
                        cache=cache, lens=_sds((b,), jnp.int32, one_chip))
    _assert_scopes(compiled, "attn", "mlp", "cast", "lm_head")


def test_fused_residual_down_projection(one_chip, pallas):
    """512x6144 @ 6144x2048 + residual: the extras count toward VMEM."""
    m, k, n = 512, 6144, 2048
    _compile(lambda h, w, r: matmul(h, w, epilogue=Epilogue(residual=True),
                                    residual=r),
             _sds((m, k), BF16, one_chip), _sds((k, n), BF16, one_chip),
             _sds((m, n), BF16, one_chip))


def test_tn_small_m(one_chip, pallas):
    """"tn" at M=8: bm is A's lane axis there, so it must be 128-aligned."""
    m, k, n = 8, 2048, 6144
    _compile(lambda a, b: matmul(a, b, trans="tn"),
             _sds((k, m), BF16, one_chip), _sds((k, n), BF16, one_chip))


def test_mixtral_capacity_grouped_swiglu(one_chip, pallas):
    """mixtral-8x7b capacity experts: 8 x 160 x 4096 -> 14336, fused."""
    e, c, d, f = 8, 160, 4096, 14336
    _compile(grouped_swiglu, _sds((e, c, d), BF16, one_chip),
             _sds((e, d, f), BF16, one_chip), _sds((e, d, f), BF16, one_chip))


def test_mixtral_ragged_swiglu(one_chip, pallas):
    """mixtral-8x7b ragged experts: 1024 routed rows over 8 groups."""
    t, e, d, f = 1024, 8, 4096, 14336
    offsets = np.linspace(0, t, e + 1).astype(np.int32)
    _compile(lambda x, wg, wu: ragged_swiglu(x, wg, wu, jnp.asarray(offsets)),
             _sds((t, d), BF16, one_chip), _sds((e, d, f), BF16, one_chip),
             _sds((e, d, f), BF16, one_chip))
