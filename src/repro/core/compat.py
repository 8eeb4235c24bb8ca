"""The few JAX surfaces the repo wraps, for the installed jax (0.9.0).

  * ``shard_map``           — ``jax.shard_map``.
  * ``shard_map_unchecked`` — ``shard_map`` with ``check_vma=False``: the
                              ftIMM ``pallas_call`` bodies carry no
                              varying-manual-axes annotations.
  * ``make_mesh``           — ``jax.make_mesh`` with every axis
                              ``jax.sharding.AxisType.Auto``.
  * ``cost_analysis``       — ``Compiled.cost_analysis()`` as a plain dict.
  * ``pallas_compiler_params`` / ``prefetch_scalar_grid_spec`` — the TPU
                              Pallas ``CompilerParams`` and
                              ``PrefetchScalarGridSpec``.
  * ``ragged_all_to_all``   — ``jax.lax.ragged_all_to_all``.
"""
from __future__ import annotations

from typing import Sequence

import jax

shard_map = jax.shard_map
ragged_all_to_all = jax.lax.ragged_all_to_all


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``shard_map`` with replication checking off (needed around the
    ``pallas_call`` bodies of the ftIMM kernels)."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with plain Auto axes."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names), devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (``{}`` when XLA gives none)."""
    return dict(compiled.cost_analysis() or {})


def pallas_compiler_params(**kwargs):
    """TPU Pallas compiler params."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)


def prefetch_scalar_grid_spec(*, num_scalar_prefetch: int, grid, in_specs,
                              out_specs, scratch_shapes=()):
    """Scalar-prefetch grid spec (index maps may read int32 operands)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=list(scratch_shapes))
