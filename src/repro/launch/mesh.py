"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because the dry-run must
set XLA_FLAGS before the first jax call, and smoke tests must see 1 device.

Every mesh axis is ``AxisType.Auto``: the sharding rules
(``distribution.sharding``) place parameters and caches and let GSPMD
propagate through the programs.  ``jax.make_mesh``'s default (``Explicit``
axes) would instead type-check every op's sharding, and the sharded
embedding gather is refused under it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use tiny ones, e.g. (2, 2) on 4 host devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Mesh over however many (host) devices exist — used by mini dry-runs."""
    n = jax.device_count()
    assert n_data * n_model <= n, (n_data, n_model, n)
    return make_mesh((n_data, n_model), ("data", "model"))
