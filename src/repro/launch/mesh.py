"""Production mesh construction.

A function (not a module-level constant) so importing never touches JAX
device state. The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax;
smoke tests and benches see the real single CPU device.
"""

from __future__ import annotations

import jax


def auto_axes(n_axes: int) -> tuple:
    """`axis_types` for a mesh whose axes are all Auto (sharding decided
    by the compiler), the way every mesh in this repository is built."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, auto_axes(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (possibly forced-host) devices exist.

    The axis product must divide the device count: `jax.make_mesh` happily
    builds a 3-device mesh on an 8-device host (silently stranding five
    devices), which downstream code then mistakes for full-host sharding.
    Raises `ValueError` naming the axis sizes and the device count when
    `data * model * pod` does not divide `len(jax.devices())`.
    """
    if data < 1 or model < 1 or pod < 0:
        raise ValueError(
            f"mesh axis sizes must be positive (pod >= 0), got "
            f"data={data} model={model} pod={pod}")
    n_devices = len(jax.devices())
    product = data * model * (pod or 1)
    if n_devices % product != 0:
        axes_s = (f"pod={pod} data={data} model={model}" if pod
                  else f"data={data} model={model}")
        raise ValueError(
            f"mesh shape {axes_s} (= {product} devices) does not divide "
            f"the {n_devices} available device(s); pick axis sizes whose "
            f"product divides the device count")
    if pod:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    return jax.make_mesh(shape, axes, auto_axes(len(axes)))
