"""Production mesh construction.

Single pod: 16 x 16 = 256 chips (v5e pod), axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — 'pod' composes
with 'data' for gradient reduction / batch sharding; XLA emits hierarchical
collectives (reduce-scatter on ICI inside the pod, all-reduce across DCN).

A FUNCTION (not a module constant) so importing never touches jax device
state — the dry-run sets XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto mode: the model code places
    activations through ``with_sharding_constraint`` and leaves gathers to
    the partitioner, which Explicit axes (``make_mesh``'s default) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever-fits mesh for CPU tests: (1, n_devices//model, model)."""
    n = len(jax.devices())
    return _auto_mesh((n // model, model), ("data", "model"))


def make_tp_mesh(model: int = 1):
    """1-D ('model',) mesh over the first ``model`` devices — the serving
    engine's tensor-parallel axis for the sharded paged KV pool.  Unlike
    ``make_host_mesh`` it does not require the total device count to divide:
    a TP=4 engine on an 8-device host takes devices [0, 4).  On CPU,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` simulates the
    devices — the recipe the test-tp CI lane runs under."""
    devs = jax.devices()
    assert 1 <= model <= len(devs), \
        f"TP={model} needs {model} devices, found {len(devs)} " \
        "(on CPU set XLA_FLAGS=--xla_force_host_platform_device_count)"
    return Mesh(np.asarray(devs[:model]), ("model",))
