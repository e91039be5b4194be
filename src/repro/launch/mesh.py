"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state — critical because the dry-run
must set XLA_FLAGS before jax initialises.
"""
from __future__ import annotations

import jax

from ..core.plan import mesh_shape_dict  # re-export: single definition

__all__ = ["make_mesh_compat", "make_production_mesh", "make_cpu_mesh",
           "mesh_shape_dict", "mesh_fingerprint", "force_host_devices",
           "parse_mesh_spec"]

#: CLI parallelism names -> mesh axis names.  The CLI speaks the
#: paper's vocabulary (dp/tp/stage); the mesh speaks jax's
#: (data/model/stage).
_MESH_AXIS_ALIASES = {"dp": "data", "data": "data",
                      "tp": "model", "model": "model",
                      "pp": "stage", "stage": "stage"}


def parse_mesh_spec(spec: str):
    """Parse ``"dp=2,tp=2,stage=2"`` into ``(shape, axis_names)``.

    Accepts both CLI aliases (dp/tp/pp) and raw axis names
    (data/model/stage), in any order; size-1 axes are dropped (a
    1-wide group is just replication — the solver prices it
    identically, see ``core.costs.send_time``).  Axis order is
    canonicalized to (data, model, stage) so equivalent specs
    fingerprint identically.
    """
    sizes = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"mesh spec entry {part!r} is not "
                             f"<axis>=<size> (spec: {spec!r})")
        name, _, val = part.partition("=")
        axis = _MESH_AXIS_ALIASES.get(name.strip().lower())
        if axis is None:
            raise ValueError(
                f"unknown mesh axis {name.strip()!r} — use "
                f"dp/tp/stage (spec: {spec!r})")
        try:
            size = int(val)
        except ValueError:
            raise ValueError(f"mesh axis {name.strip()!r} has non-"
                             f"integer size {val!r}") from None
        if size < 1:
            raise ValueError(f"mesh axis {name.strip()!r} has size "
                             f"{size} < 1")
        if axis in sizes:
            raise ValueError(f"mesh axis {axis!r} given twice in "
                             f"{spec!r}")
        sizes[axis] = size
    canon = [(a, sizes[a]) for a in ("data", "model", "stage")
             if sizes.get(a, 1) > 1]
    if not canon:
        raise ValueError(f"mesh spec {spec!r} names no axis wider "
                         f"than 1 device")
    return tuple(s for _, s in canon), tuple(a for a, _ in canon)


def force_host_devices(n: int) -> None:
    """Ensure XLA_FLAGS requests at least ``n`` fake host devices.

    Must run before jax initialises its backends (flags are read at
    backend init, not at ``import jax``).  A pre-existing
    ``--xla_force_host_platform_device_count`` with a *smaller* count
    is replaced — the caller's mesh needs ``n`` — while a larger one is
    kept; on real accelerator hosts the flag only affects the unused
    CPU platform, so forcing is always safe.  Single home for this
    mangling: the serve CLI and the sharding benchmark both route
    through here.
    """
    import os
    import re
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m:
        if int(m.group(1)) >= n:
            return
        flags = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}")
    else:
        flags = (f"{flags} "
                 f"--xla_force_host_platform_device_count={n}").strip()
    os.environ["XLA_FLAGS"] = flags


def make_mesh_compat(shape, axis_names):
    """``jax.make_mesh`` with every axis Auto, the only mode this repo
    uses (GSPMD and ``shard_map`` place the collectives)."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256-chip single pod; 2x16x16 = 512-chip two-pod mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_cpu_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (fake) devices the test process has."""
    return make_mesh_compat((data, model), ("data", "model"))


def mesh_fingerprint(mesh) -> str:
    """Stable cache-key component for a mesh: platform + axis topology.

    Device *ids* are deliberately excluded — the same topology on a
    different pod (or a restarted fake-device process) solves identical
    placement PBQPs, so its persisted plans stay valid.
    """
    if mesh is None:
        return "none"
    axes = "x".join(f"{n}{s}" for n, s in
                    zip(mesh.axis_names, mesh.devices.shape))
    platform = mesh.devices.flat[0].platform
    return f"{platform}-{axes}"
