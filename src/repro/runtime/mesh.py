"""One mesh-construction API for the whole repo.

Absorbs the logic that used to live in ``launch/mesh.py`` (production
16x16 / 2x16x16 grids) and ``launch/solve.py`` (ad-hoc local meshes):

    make_mesh({"data": 2, "model": 4})              # preferred form
    make_mesh((2, 4), ("data", "model"))            # legacy positional
    make_mesh({"data": 8}, backend="cpu")           # platform-filtered
    make_local_mesh()                               # all local devices

Device-count errors point at the CPU multi-device fallback
(``request_cpu_devices`` / XLA_FLAGS) instead of XLA's opaque reshape
failure.  Every mesh is built with ``AxisType.Auto`` axes (GSPMD
propagation), which is what the shard_map solvers and the
``constrain`` annotations below are written for.
"""
from __future__ import annotations

import math
import os
import re
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

AxesSpec = Union[Dict[str, int], Sequence[int]]


def _normalize_axes(axes: AxesSpec, names: Optional[Sequence[str]]):
    if isinstance(axes, dict):
        return tuple(axes.values()), tuple(axes.keys())
    axes = tuple(axes)
    if names is not None:
        return axes, tuple(names)
    if axes and isinstance(axes[0], (tuple, list)):  # [("data", 2), ...]
        return tuple(int(s) for _, s in axes), tuple(a for a, _ in axes)
    raise TypeError(
        "make_mesh expects a {name: size} dict, (shape, names), or a "
        f"sequence of (name, size) pairs; got {axes!r}")


def make_mesh(axes: AxesSpec, names: Optional[Sequence[str]] = None, *,
              backend: Optional[str] = None, devices=None):
    """Build a Mesh on any supported JAX, with readable capacity errors."""
    shape, axis_names = _normalize_axes(axes, names)
    needed = math.prod(shape)
    if devices is None:
        devices = jax.devices(backend) if backend is not None else None
    avail = len(devices) if devices is not None else len(jax.devices())
    if needed > avail:
        raise RuntimeError(
            f"mesh {dict(zip(axis_names, shape))} needs {needed} devices "
            f"but only {avail} are visible"
            + (f" on backend {backend!r}" if backend else "")
            + "; for CPU tests set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={needed} before the "
            "first device query (repro.runtime.mesh.request_cpu_devices)")
    if devices is not None:
        devices = list(devices)[:needed]
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(shape, axis_names, axis_types=auto, devices=devices)


def _default_pod_count() -> int:
    """Pod axis = process granularity.  Single-process keeps the legacy
    2-pod dry-run grid (512 fake devices); in a real multi-process
    cluster the pod axis matches ``jax.process_count()``."""
    from . import cluster

    n = cluster.pod_count()
    return n if n > 1 else 2


def make_production_mesh(*, multi_pod: bool = False,
                         pods: Optional[int] = None,
                         grid: Tuple[int, int] = (16, 16),
                         backend: Optional[str] = None):
    """16x16 = 256 chips/pod; ``multi_pod`` adds a leading pod axis.

    The pod axis is derived from the process count (one pod per
    process; the old hard-coded 2 survives only as the single-process
    dry-run default) — override with ``pods``.  ``grid`` shrinks the
    per-pod chip grid for tests.
    """
    rows, cols = grid
    if multi_pod:
        if pods is None:
            pods = _default_pod_count()
        return make_mesh({"pod": int(pods), "data": rows, "model": cols},
                         backend=backend)
    return make_mesh({"data": rows, "model": cols}, backend=backend)


def make_cluster_mesh(axis_names: Tuple[str, ...] = ("pod", "data", "model"),
                      *, backend: Optional[str] = None):
    """Process-spanning mesh: pod axis = process granularity.

    Devices are ordered by ``(process_index, id)`` so each pod's block
    is exactly one process's addressable devices — shard placement along
    the pod axis never needs cross-process transfers at setup.  Within a
    pod the local devices form a near-square (data, model) grid.  Falls
    back to a 1-pod mesh over the local devices when single-process, so
    callers need no separate code path.
    """
    devs = sorted(jax.devices(backend) if backend else jax.devices(),
                  key=lambda d: (d.process_index, d.id))
    pods = max(1, getattr(jax, "process_count", lambda: 1)())
    per_pod = len(devs) // pods
    if per_pod * pods != len(devs):
        raise RuntimeError(
            f"{len(devs)} global devices do not divide into {pods} pods; "
            "heterogeneous pods are not supported")
    rows = max(1, per_pod // 2)
    while per_pod % rows:
        rows -= 1
    cols = per_pod // rows
    return make_mesh((pods, rows, cols), axis_names, devices=devs)


def make_local_mesh(axis_names: Tuple[str, str] = ("data", "model"), *,
                    backend: Optional[str] = None):
    """Near-square 2-D mesh over all visible devices (local solves)."""
    n_dev = len(jax.devices(backend) if backend else jax.devices())
    rows = max(1, n_dev // 2)
    while n_dev % rows:
        rows -= 1
    cols = n_dev // rows
    return make_mesh((rows, cols), axis_names, backend=backend)


# ------------------------------------------------- sharding annotations ---

def mesh_axis_names() -> Tuple[str, ...]:
    """Axis names of the ambient mesh (``jax.set_mesh``); () without one."""
    return tuple(jax.sharding.get_abstract_mesh().axis_names)


def clean_spec(spec, names) -> P:
    """Drop spec axes absent from ``names`` (e.g. 'pod' on single-pod)."""
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
        elif isinstance(s, tuple):
            t = tuple(a for a in s if a in names)
            clean.append(t if t else None)
        else:
            clean.append(s if s in names else None)
    return P(*clean)


def constrain(x, *spec):
    """``with_sharding_constraint`` against the ambient mesh; a no-op
    without one.  Axes absent from the mesh are dropped; non-divisible
    dims are padded internally by GSPMD (e.g. 40 heads on a 16-way
    axis)."""
    names = mesh_axis_names()
    if not names:
        return x
    return jax.lax.with_sharding_constraint(x, clean_spec(spec, names))


def batch_axes() -> Tuple[str, ...]:
    """The data-parallel axes present on the ambient mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh_axis_names())


# -------------------------------------------------- CPU device fan-out ---

def request_cpu_devices(n: int) -> bool:
    """Ask XLA for ``n`` host-platform (CPU) devices.

    Must run before the first device query in the process (the flag is
    read at backend initialization).  Returns False when the backend is
    already up, in which case the caller should re-exec in a subprocess.
    """
    from jax._src import xla_bridge

    flag = f"--xla_force_host_platform_device_count={n}"
    cur = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in cur:
        # rewrite a pre-existing (possibly different) count in place
        cur = re.sub(r"--xla_force_host_platform_device_count=\d+",
                     flag, cur)
        os.environ["XLA_FLAGS"] = cur
    else:
        os.environ["XLA_FLAGS"] = (cur + " " + flag).strip()
    return not xla_bridge.backends_are_initialized()
