"""Production mesh construction. A function — importing this module never
touches jax device state."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto-sharded."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    return make_mesh(shape, axes)


def make_serving_mesh(n_devices=None, *, model: int = 1, pod: int = 1):
    """Mesh for the sharded serving arena (docs/sharding.md): axes
    ('data', 'model') — with a leading 'pod' when `pod > 1` — where the
    data extent soaks up every device not claimed by `model`/`pod`. Arena
    slots shard over all axes; the lm head is vocab-parallel over 'model';
    a pod ring carries the cut activation across the pod boundary."""
    n = n_devices if n_devices is not None else len(jax.devices())
    if n % (model * pod):
        raise ValueError(f"{n} devices not divisible by model={model} x "
                         f"pod={pod}")
    data = n // (model * pod)
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
