"""Declarative parameter specs: one source of truth for shapes, init and
logical sharding axes.

A model's parameters are described as a nested dict of :class:`ParamSpec`.
From the same spec tree we derive:
  * ``init_params``      — materialized arrays (jax.random)
  * ``logical_axes``     — pytree of logical-axis-name tuples (for sharding)
  * ``abstract_params``  — ShapeDtypeStructs (for dry-run, no allocation)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.util import fold_in_str


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]  # logical axis name per dim (None = replicated)
    init: str = "normal"  # normal|zeros|ones|small_normal|mamba_dt|mamba_alog
    scale: float = 1.0
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _materialize(spec: ParamSpec, key: jax.Array) -> jax.Array:
    dtype = jnp.dtype(spec.dtype)
    shape = spec.shape
    if spec.init == "zeros":
        return jnp.zeros(shape, dtype)
    if spec.init == "ones":
        return jnp.ones(shape, dtype)
    if spec.init == "normal":
        # fan-in scaled normal
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        std = spec.scale / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
    if spec.init == "small_normal":
        return (jax.random.normal(key, shape, jnp.float32) * 0.02 * spec.scale).astype(dtype)
    if spec.init == "mamba_dt":
        # dt bias init: softplus^-1 of uniform in [1e-3, 1e-1]
        u = jax.random.uniform(key, shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(u)).astype(dtype)
    if spec.init == "mamba_alog":
        # A_log init: log of uniform [1, 16]
        u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    raise ValueError(f"unknown init {spec.init}")


def is_spec_leaf(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def init_params(specs: Any, key: jax.Array) -> Any:
    """Materialize a spec tree into arrays, deterministically keyed by path."""
    flat, treedef = jax.tree.flatten_with_path(specs, is_leaf=is_spec_leaf)
    leaves = []
    for path, spec in flat:
        pkey = fold_in_str(key, jax.tree_util.keystr(path))
        leaves.append(_materialize(spec, pkey))
    return jax.tree.unflatten(treedef, leaves)


def logical_axes(specs: Any) -> Any:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec_leaf)


def abstract_params(specs: Any) -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)), specs, is_leaf=is_spec_leaf
    )


def stack_specs(specs: Any, n: int, axis_name: Optional[str] = "layers") -> Any:
    """Add a leading stacking dim (for scan-over-superblocks) to every spec."""

    def stack_one(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.init, s.scale, s.dtype)

    return jax.tree.map(stack_one, specs, is_leaf=is_spec_leaf)
