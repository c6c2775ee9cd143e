"""Mesh builders: production pods (cross-silo) and the fleet client mesh.

This module imports NO jax at module scope, so it can be imported *before*
jax to request forced host devices: ``force_host_platform_device_count(n)``
edits ``XLA_FLAGS`` and raises if jax was already initialized (the flag is
read once at backend creation — setting it later silently does nothing,
which is exactly the doc-only folklore this helper replaces).  Benchmarks
and tests that want a multi-device fleet on CPU call it first, then import
jax / build the mesh::

    from repro.launch.mesh import force_host_platform_device_count
    force_host_platform_device_count(8)          # before any jax import
    from repro.launch.mesh import make_fleet_mesh
    mesh = make_fleet_mesh(8)                    # ("clients",) axis

``make_production_mesh`` / ``make_host_mesh`` / ``make_fleet_mesh`` are
FUNCTIONS so importing this module never touches jax device state; callers
(dryrun / train / serve / FleetEngine) decide when the mesh is built.
"""
from __future__ import annotations

import os
import re
import sys
from typing import Optional

_FORCE_RE = re.compile(r"--xla_force_host_platform_device_count=\d+")


def force_host_platform_device_count(n: int) -> None:
    """Request ``n`` host platform devices — call before jax *initializes*.

    Appends/rewrites ``--xla_force_host_platform_device_count`` in
    ``XLA_FLAGS``.  The flag is read once, when the CPU client is created
    (the first jax computation / ``jax.devices()`` call), not at import —
    so the env edit happens unconditionally, and when jax is already
    loaded the device count is probed afterwards: if the backend had
    already been created with the old flags this raises instead of
    silently handing back a wrong-sized fleet.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    new = f"--xla_force_host_platform_device_count={n}"
    if _FORCE_RE.search(flags):
        flags = _FORCE_RE.sub(new, flags)
    else:
        flags = (flags + " " + new).strip()
    os.environ["XLA_FLAGS"] = flags
    if "jax" in sys.modules:
        import jax  # initializes the backend NOW if it wasn't yet
        if len(jax.devices()) != n:
            raise RuntimeError(
                f"force_host_platform_device_count({n}) called after jax "
                f"was initialized ({len(jax.devices())} device(s)); set "
                f"it before the first jax use, or spawn a subprocess "
                f"(see tests/test_mesh_engine.py)")


def _auto(n: int):
    """``Auto`` axis types for an n-axis mesh: the round path steers
    placement with ``with_sharding_constraint``, which only Auto axes
    accept (``jax.make_mesh`` defaults to Explicit axes)."""
    from jax.sharding import AxisType
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips; two pods: (2, 16, 16) = 512."""
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    import jax
    n = len(jax.devices())
    data = min(data, n)
    model = max(min(model, n // data), 1)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def make_fleet_mesh(num_devices: Optional[int] = None):
    """1-D ``("clients",)`` mesh for the cross-device FL round path.

    The fleet's stacked client pytree, the packed (C, D) aggregation
    buffer, and all (N,) per-client state shard over this axis (see
    ``repro.sharding.partitioning.fleet_*``).  ``num_devices=None`` takes
    every visible device; asking for more than exist raises.
    """
    import jax
    avail = len(jax.devices())
    n = avail if num_devices is None else int(num_devices)
    if n < 1 or n > avail:
        raise ValueError(f"make_fleet_mesh({num_devices}): {avail} "
                         f"device(s) visible")
    return jax.make_mesh((n,), ("clients",), axis_types=_auto(1))


def n_silos(mesh) -> int:
    """FL silos = product of the (pod, data) axes."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1) * sizes.get("data", 1)
