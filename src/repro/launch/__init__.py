"""repro.launch — executable entry points for the model stack.  `train`
runs real (CPU-scale, reduced-config) optimization; `serve` runs batched
greedy decoding; `dryrun` lowers/compiles every (arch x shape) on the
production mesh without executing (the 512-virtual-device coherence
proof); `specs` and `hlo_stats` are its supporting shape/cost
tooling (mesh builders live in `repro.distributed.mesh`).
The paper-experiment entry point is separate:
``python -m repro.experiments.run`` (``--devices N`` shards it over a
device mesh via `repro.distributed`).
"""
