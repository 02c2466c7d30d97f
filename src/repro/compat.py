"""The few JAX mesh and typing helpers the repo shares.

  make_mesh(...)              ``jax.make_mesh`` with implicit (Auto) axes —
                              JAX's own default is Explicit axes, which this
                              repo's sharding rules do not use
  abstract_mesh(...)          device-free mesh for lowering-only benchmarks
  pcast_varying(x, axes)      cast ``x`` to device-varying over ``axes``
                              (a no-op for axes it already varies over)
  ragged_executes()           whether the default backend can execute
                              ``lax.ragged_all_to_all`` (XLA:CPU lowers the
                              op but cannot run it)
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType

__all__ = ["abstract_mesh", "make_mesh", "pcast_varying", "ragged_executes"]

# backends whose runtime implements ragged_all_to_all
_RAGGED_BACKENDS = ("tpu", "gpu")


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *, axis_types=None, devices=None):
    """``jax.make_mesh`` whose axes default to ``AxisType.Auto``."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types, devices=devices)


def abstract_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]) -> AbstractMesh:
    """A device-free mesh usable for ``.lower()`` (no execution)."""
    names = tuple(axis_names)
    return AbstractMesh(
        tuple(axis_shapes), names, axis_types=(AxisType.Auto,) * len(names)
    )


def pcast_varying(x, axes):
    """Cast ``x`` to device-varying over ``axes`` it does not vary over yet."""
    missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def ragged_executes() -> bool:
    """True when JAX's default backend can run ``lax.ragged_all_to_all``."""
    return jax.default_backend() in _RAGGED_BACKENDS
