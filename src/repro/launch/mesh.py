"""Production mesh definitions (task §MULTI-POD DRY-RUN).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.
"""

from __future__ import annotations

import jax


def _auto(n: int):
    """Auto axes: the sharding rules constrain with ``with_sharding_constraint``,
    which ``jax.make_mesh``'s default Explicit axes refuse."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(len(axes)))


def make_mesh_for(devices: int, model_parallel: int = None):
    """Elastic helper: best (data, model) mesh for an arbitrary device count."""
    model = model_parallel or min(devices, 16)
    while devices % model:
        model //= 2
    data = devices // model
    return jax.make_mesh((data, model), ("data", "model"), _auto(2))
