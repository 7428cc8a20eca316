"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required for the dry-run flow, where the
placeholder device count must be set before the first jax initialization.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    # Auto axis types: the framework mixes GSPMD-constrained jit code with
    # explicit shard_map blocks (the XYZ matmul), which requires Auto.
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16 x 16 = 256 chips, axes (data, model).
    Multi-pod: 2 x 16 x 16 = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(data: int, model: int, pod: int = 1) -> Mesh:
    """Arbitrary (pod x) data x model mesh — used by tests, examples and
    elastic restarts on whatever devices remain."""
    if pod > 1:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


def make_host_mesh() -> Mesh:
    """Whatever this host offers, as a (data, 1) mesh (CPU tests)."""
    n = jax.device_count()
    return _mk((n, 1), ("data", "model"))
