"""Logical-axis activation sharding constraints.

Model code annotates intermediates with LOGICAL axis names
(``constrain(x, "batch", None, "model")``); the launch layer decides which
mesh axes are live via the ``activation_sharding`` context manager.  Outside
the context — or with no device mesh — every call is a no-op, so the same
model code runs unmodified on one CPU device and on a 512-device mesh.

Logical → mesh translation:

  ``batch``  → every live data-parallel axis, in mesh order (``pod``, ``data``)
  ``seq``    → the tensor axis (``model``) — Megatron sequence parallelism
  ``model`` / ``data`` / ``pod`` → themselves, when live

A constraint is silently dropped per-dimension when the mapped mesh axes do
not evenly divide that dimension, or when the mesh axis is already used by an
earlier dimension of the same array (GSPMD would reject both).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["activation_sharding", "axis_size", "constrain"]

# data-parallel mesh axes in the order they appear in production meshes
_BATCH_AXES = ("pod", "data")
_LOGICAL = {"batch": _BATCH_AXES, "seq": ("model",)}


class _Ctx(threading.local):
    def __init__(self):
        self.axes: Optional[Tuple[str, ...]] = None
        self.sizes: Optional[Dict[str, int]] = None


_CTX = _Ctx()


def _ambient_mesh_shape() -> Dict[str, int]:
    """Axis sizes of the mesh set with ``jax.set_mesh``, if any."""
    mesh = jax.sharding.get_abstract_mesh()
    return {} if mesh.empty else dict(mesh.shape)


def _mesh_sizes() -> Dict[str, int]:
    return _CTX.sizes if _CTX.sizes else _ambient_mesh_shape()


@contextlib.contextmanager
def activation_sharding(axes: Sequence[str], sizes: Optional[Dict[str, int]] = None):
    """Declare which mesh axes activation constraints may target.

    ``axes``: live mesh axis names (usually ``mesh.axis_names``).
    ``sizes``: optional ``{axis: size}`` for divisibility checks; defaults to
    the ambient mesh set with ``jax.set_mesh``.
    """
    prev = (_CTX.axes, _CTX.sizes)
    _CTX.axes = tuple(axes)
    _CTX.sizes = dict(sizes) if sizes else None
    try:
        yield
    finally:
        _CTX.axes, _CTX.sizes = prev


def _resolve(name: Optional[str]) -> Tuple[str, ...]:
    """Logical activation axis -> tuple of live mesh axes (may be empty)."""
    if name is None or _CTX.axes is None:
        return ()
    mesh_names = _LOGICAL.get(name, (name,))
    return tuple(a for a in mesh_names if a in _CTX.axes)


def axis_size(name: str) -> int:
    """Product of mesh-axis sizes a logical axis maps to; 0 when inactive.

    Model code uses this for layout decisions (e.g. head-sharded vs
    sequence-sharded attention when ``n_heads % axis_size("model")``).
    """
    mesh_axes = _resolve(name)
    if not mesh_axes:
        return 0
    sizes = _mesh_sizes()
    if not sizes:
        return 0
    prod = 1
    for a in mesh_axes:
        prod *= int(sizes.get(a, 1))
    return prod


def constrain(x, *axes):
    """``with_sharding_constraint`` by logical axis names; no-op without a
    live ``activation_sharding`` context or mesh."""
    if _CTX.axes is None:
        return x
    sizes = _mesh_sizes()
    if not sizes:
        return x
    from .sharding import _enforce_one  # shared drop rules (dup/absent/indivisible)

    raw = P(*(_resolve(name) or None for _, name in zip(x.shape, axes)))
    spec = _enforce_one(tuple(x.shape), raw, sizes)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)
