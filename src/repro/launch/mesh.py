"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required for the smoke
tests (1 device) and the dry-run (512 placeholder devices) to coexist.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _make(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests, pipeline demos, elastic restore targets)."""
    return _make(shape, axes)


def single_device_mesh():
    return make_mesh((1, 1), ("data", "model"))


def make_serving_mesh(tp: Optional[int] = None):
    """1-D tensor-parallel mesh for the serving engine: ``("model",)``
    over ``tp`` devices (default: all visible). Serving has no data axis
    — every device holds the same slots and a shard of every weight and
    of the KV page pool; ``tp=1`` returns None so the engine takes its
    unsharded (mesh-blind) path rather than a degenerate shard_map."""
    tp = tp if tp is not None else jax.device_count()
    if tp <= 1:
        return None
    assert tp <= jax.device_count(), (tp, jax.device_count())
    return make_mesh((tp,), ("model",))


def describe(mesh) -> str:
    return "x".join(f"{k}={v}" for k, v in mesh.shape.items())
