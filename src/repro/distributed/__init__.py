"""repro.distributed — device-mesh execution for sweeps and the model stack.

The reproduction of a scalability paper should itself scale: this package
shards the sweep engine's batched (m-grid x seed) simulations across every
available XLA device while keeping results **mesh-invariant** — the same
spec produces the same curves (1e-5) and the same cache fingerprint on 1
device or 8 (docs/distributed.md spells out the contract; CI runs the
suite under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

  `mesh`            :class:`DeviceMesh` — the 1-D sweep mesh: auto-detected
                    (:func:`get_mesh`), overridable (``--devices N``),
                    single-device fallback that is bit-exact with the
                    unsharded engine path.  Also hosts the model stack's
                    named-mesh builders (absorbed from `repro.launch.mesh`).
  `partition`       the grid partitioner: splits each bucket's seeds over
                    the mesh when they divide evenly, else flattens its
                    (members x seeds) cells into one padded element axis
                    laid over the mesh; one jit per bucket.
  `hogwild_shards`  TRUE multi-device Hogwild! — worker shards racing on a
                    donated shared parameter under ``shard_map``; the
                    engine's sequential staleness recurrence remains the
                    parity oracle.
  `rules`           the model stack's FSDP/TP PartitionSpec rules (absorbed
                    from the former ``repro.sharding``).

Execution never enters result identity: `repro.experiments.spec`
fingerprints exclude the ``devices`` field, so a sweep cached on one mesh
is a hit on any other.
"""

from repro.distributed.hogwild_shards import (run_hogwild_sharded,
                                              sweep_hogwild_sharded)
from repro.distributed.mesh import (SHARD_AXIS, DeviceMesh, MeshLike,
                                    from_devices, get_mesh,
                                    make_debug_mesh, make_production_mesh,
                                    resolve)
from repro.distributed.partition import (element_plan, layout,
                                         pad_to_multiple, run_grid_sharded)
from repro.distributed.rules import (FSDP_AXES, act_constraint, batch_specs,
                                     data_axes, decode_act_constraint,
                                     decode_state_specs, head_constraint,
                                     inner_act_constraint, layer_constraint,
                                     logits_constraint, opt_state_specs,
                                     param_specs)

__all__ = [
    "SHARD_AXIS", "DeviceMesh", "MeshLike", "from_devices", "get_mesh",
    "resolve", "make_debug_mesh", "make_production_mesh",
    "element_plan", "layout", "pad_to_multiple", "run_grid_sharded",
    "run_hogwild_sharded", "sweep_hogwild_sharded",
    "FSDP_AXES", "act_constraint", "batch_specs", "data_axes",
    "decode_act_constraint", "decode_state_specs", "head_constraint",
    "inner_act_constraint", "layer_constraint", "logits_constraint",
    "opt_state_specs", "param_specs",
]
