"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialisation.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: pod = pure DP across pods; data = FSDP; model = TP(+EP)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — the "
            "dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax")
    return _make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests / CPU training)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return _make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def serving_devices(n: int, devices=None, *, oversubscribe: bool = True):
    """The device list for an ``n``-replica serving cluster.

    ``devices`` pins an explicit list (must hold at least ``n``; the first
    ``n`` are used — the caller controls placement).  With the default
    ``devices=None`` the visible ``jax.devices()`` are dealt out
    round-robin; when ``n`` exceeds the device count, ``oversubscribe``
    (default, the CPU-test posture — also how the CI cluster smoke runs
    before XLA_FLAGS forces extra host devices) reuses devices cyclically,
    while ``oversubscribe=False`` raises — the production posture, where a
    "replica" that silently shares a device is a capacity-planning bug.
    On a TPU it always raises: replicas sharing a chip would report one
    chip's throughput as several.
    """
    if n < 1:
        raise ValueError(f"need n >= 1 replicas, got {n}")
    if devices is not None:
        devices = list(devices)
        if len(devices) < n:
            raise ValueError(
                f"need {n} devices for {n} replicas, got {len(devices)} "
                f"explicit devices")
        return devices[:n]
    avail = jax.devices()
    if len(avail) < n and avail[0].platform == "tpu":
        raise RuntimeError(
            f"need {n} TPU chips for {n} replicas, have {len(avail)}; "
            "replicas never share a chip")
    if len(avail) < n and not oversubscribe:
        raise RuntimeError(
            f"need {n} devices for {n} replicas, have {len(avail)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N for CPU "
            "testing, or pass oversubscribe=True to share devices")
    return [avail[i % len(avail)] for i in range(n)]


def make_serving_mesh(n: int, devices=None, *,
                      oversubscribe: bool = True) -> Mesh:
    """1-D ``("replica",)`` mesh over the serving cluster's devices.

    Each coordinate along the ``replica`` axis is one serving replica's
    device (``serving_devices`` picks them); per-replica parameter
    placement then falls out of ``sharding.partition.replica_shardings``.
    Requires ``n`` DISTINCT devices — a jax mesh cannot repeat a device,
    so the oversubscribed CPU-test posture skips the mesh and pins each
    replica directly (``sharding.partition.pin_to_device``)."""
    devs = serving_devices(n, devices, oversubscribe=oversubscribe)
    if len(set(d.id for d in devs)) != len(devs):
        raise RuntimeError(
            f"make_serving_mesh needs {n} distinct devices (a mesh cannot "
            "repeat one); oversubscribed replicas are pinned directly via "
            "sharding.partition.pin_to_device instead")
    return _make_mesh((n,), ("replica",), devices=devs)
