"""Production mesh definitions.

Single pod: (16, 16) = 256 chips, axes ('data', 'model') — TP inside the
fast ICI dimension, FSDP over 'data'.  Multi-pod: (2, 16, 16) = 512
chips, axes ('pod', 'data', 'model') — only gradient all-reduce (train)
or pure batch parallelism (serve) crosses the slow 'pod' (DCN-class)
axis.  Serving: a 1-D ('data',) mesh over the host's addressable
devices — CNN inference is embarrassingly batch-parallel, so the
sharded bucket programs (serve/distributed.py) never need a model axis.
Defined as functions so importing this module never touches jax device
state.
"""
from __future__ import annotations

import jax
import numpy as np

#: the one mesh axis the serving layer shards over (batch data-parallel)
SERVE_AXIS = "data"


def _auto_mesh(shape, axes):
    # Auto axis types: sharding follows the in/out_shardings and
    # with_sharding_constraint annotations (jax.make_mesh's Explicit
    # default types every array and refuses gathers over sharded axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = min(model, n)
    return _auto_mesh((n // model, model), ("data", "model"))


def make_serve_mesh(n_devices: int | None = None):
    """The 1-D local data-parallel serving mesh: axis ``'data'`` over
    this host's addressable devices (the first ``n_devices`` of them).

    Every sharded bucket program shards its batch axis over this mesh
    and replicates params; there is deliberately no model axis — at
    serving batch sizes the collective-free layout wins.  On CPU CI the
    same mesh forms over ``--xla_force_host_platform_device_count=N``
    forced host devices, which is what makes the whole distributed
    subsystem testable without accelerators.
    """
    devs = jax.local_devices()
    n = n_devices or len(devs)
    if not 1 <= n <= len(devs):
        raise ValueError(f"n_devices must be in [1, {len(devs)}]; got {n}")
    return jax.sharding.Mesh(np.array(devs[:n]), (SERVE_AXIS,))
