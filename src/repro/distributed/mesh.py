"""Device meshes for the sweep engine and the model stack.

Two families live here:

**Sweep mesh** (:class:`DeviceMesh`, :func:`get_mesh`) — the 1-D
``('shard',)`` mesh `repro.distributed.partition` shards the engine's
batched (m-grid x seed) simulations over.  It is auto-detected from
``jax.devices()`` (``devices="auto"``), overridable to any prefix of the
device list (``devices=4``), and degrades to an explicit *single-device
fallback* (``n_devices == 1``) in which the engine takes today's
unsharded code path bit-exactly.  The mesh is an **execution resource,
never part of result identity**: spec fingerprints exclude it (see
`repro.experiments.spec.EXECUTION_ONLY_FIELDS`) and the invariance
contract (docs/distributed.md) pins results across mesh sizes at 1e-5.

**Model-stack meshes** (:func:`make_production_mesh`,
:func:`make_debug_mesh`) — the named ('pod','data','model') meshes the
`repro.train` / `repro.launch` stack lays FSDP/TP shardings over
(absorbed from the former ``repro.launch.mesh``).  These are FUNCTIONS,
not module-level constants: importing this module never touches jax
device state (device count is locked on first jax init, and smoke tests
must see 1 CPU device, not 512).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

#: the sweep mesh's single axis name (the batched grid-element axis)
SHARD_AXIS = "shard"


@dataclasses.dataclass(frozen=True)
class DeviceMesh:
    """A 1-D mesh over the engine's batched seed or grid-element axis.

    Thin, picklable-ish wrapper around ``jax.sharding.Mesh((n,),
    ('shard',))`` carrying the derived shardings the partitioner needs.
    ``n_devices == 1`` is the *fallback signal*: the engine bypasses the
    partitioner entirely and runs the exact unsharded path.
    """

    mesh: Mesh

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def devices(self):
        return tuple(self.mesh.devices.flat)

    def sharding(self) -> NamedSharding:
        """Leading-axis sharding: grid-element indices, or draws stacked
        on their seed axis."""
        return NamedSharding(self.mesh, P(SHARD_AXIS))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def describe(self) -> str:
        """One-line ``--list``-style report (printed at CLI startup)."""
        devs = self.devices
        kinds = sorted({d.platform for d in devs})
        ids = ", ".join(str(d.id) for d in devs[:8])
        if len(devs) > 8:
            ids += ", ..."
        mode = ("single-device fallback (unsharded engine path)"
                if self.n_devices == 1 else
                f"sharding grid elements over axis {SHARD_AXIS!r}")
        return (f"mesh: {self.n_devices} x {'/'.join(kinds)} device"
                f"{'s' if self.n_devices != 1 else ''} [{ids}] — {mode}")


MeshLike = Union[None, str, int, DeviceMesh]


#: one-shot flag for the over-subscription clamp warning — a sweep over
#: many specs should say it once, not once per job (tests reset it)
_CLAMP_WARNED = False


def get_mesh(devices: MeshLike = None) -> DeviceMesh:
    """Resolve a sweep mesh from a ``--devices``-style request.

    ``None`` / ``"auto"`` take every available XLA device; an int takes
    the first ``devices`` of them (so 1 forces the single-device
    fallback on any host); a :class:`DeviceMesh` passes through.
    Requesting more devices than exist **clamps to what the host has**
    with a one-shot warning (graceful degradation: results are
    mesh-invariant, so a spec tuned for an 8-device host still runs —
    just slower — on a laptop); on a CPU container the full request can
    be honored via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    *before* the first jax import.
    """
    global _CLAMP_WARNED
    if isinstance(devices, DeviceMesh):
        return devices
    avail = jax.devices()
    if devices is None or devices == "auto":
        n = len(avail)
    else:
        n = int(devices)
        if n < 1:
            raise ValueError(f"devices={devices!r} must be >= 1")
        if n > len(avail):
            if not _CLAMP_WARNED:
                warnings.warn(
                    f"devices={n} requested but only {len(avail)} XLA "
                    f"device{'s' if len(avail) != 1 else ''} available — "
                    f"clamping to {len(avail)} (results are mesh-invariant; "
                    f"on CPU set XLA_FLAGS=--xla_force_host_platform_"
                    f"device_count={n} before the first jax import to honor "
                    f"the request)", RuntimeWarning, stacklevel=2)
                _CLAMP_WARNED = True
            n = len(avail)
    return from_devices(avail[:n])


def from_devices(devs: Sequence) -> DeviceMesh:
    """Build the 1-D sweep mesh over an explicit device list."""
    import numpy as np
    return DeviceMesh(Mesh(np.asarray(devs), (SHARD_AXIS,)))


def resolve(mesh: MeshLike) -> Optional[DeviceMesh]:
    """Engine-side resolution: ``None`` means "no distribution requested"
    (not "auto") so every existing caller keeps the unsharded path."""
    if mesh is None:
        return None
    return get_mesh(mesh)


# ---------------------------------------------------------------------------
# Model-stack meshes (absorbed from the former repro.launch.mesh)
# ---------------------------------------------------------------------------

def _auto_mesh(shape, axes):
    # Auto axes: the model stack places arrays with sharding constraints,
    # which `jax.make_mesh`'s default Explicit axes refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data=2, model=2, pod=0):
    """Small mesh for tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
