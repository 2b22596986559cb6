"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state).  Single pod: (data=16, model=16) = 256 chips; multi-pod adds
a leading ``pod`` axis (2 x 256 = 512 chips).  The ``model`` axis carries
the paper's partitioning; ``data``/``pod`` carry batch / replica
parallelism with hierarchical gradient reduction across the pod boundary
(the paper's groups-of-4 tree, one level up).
"""
from __future__ import annotations

import jax

AUTO = None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: shardings propagate through
    eager ops and ``jit`` without explicit-axis annotations."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def single_device_mesh():
    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def host_mesh(tp: int = 1, dp: int = 1):
    """Mesh over however many host devices exist (tests / examples)."""
    n = len(jax.devices())
    assert tp * dp <= n, (tp, dp, n)
    return make_mesh((dp, tp), ("data", "model"),
                     devices=jax.devices()[: tp * dp])
