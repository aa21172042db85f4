"""repro.experiments — the unified scalability-sweep engine.

This package turns the paper's experiments (worker-count m x dataset
character x algorithm x objective) into declarative, cacheable sweeps:
`spec` defines the :class:`SweepSpec` language and dataset materialization,
`registry` names one spec per paper figure/table, `engine` runs any
registered `Algorithm` on any registered `Problem` over the whole worker
grid as bucketed vmapped simulations (`engine.sweep` is the generic entry
point), `runner.run_sweep` orchestrates a spec end to end with
content-hashed artifact caching, and ``python -m repro.experiments.run``
is the CLI that reproduces any figure from a spec name.
Specs with ``n_seeds > 1`` replicate every curve over a vmapped seed
batch; `repro.analysis` consumes the replicate blocks (bootstrap CIs,
scaling-law fits, ``python -m repro.analysis.report``).

Extending it is registration, not engine surgery: a new optimizer is an
`Algorithm` dataclass (`repro.core.algorithms.base.register_algorithm`), a
new objective a `Problem` dataclass (`repro.core.problems.
register_problem`), a new dataset scenario a decorated generator
(`repro.data.synth.register_generator`).  See docs/architecture.md for
the <=30-line recipes.
"""

from repro.experiments.registry import SPEC_IDS, get_spec
from repro.experiments.runner import curves_by_m, run_sweep
from repro.experiments.spec import (ALGORITHMS, PROBLEMS, DatasetSpec,
                                    EpsilonSpec, JobSpec, SweepSpec,
                                    fingerprint, registry_signature)

__all__ = ["SPEC_IDS", "get_spec", "run_sweep", "curves_by_m", "ALGORITHMS",
           "PROBLEMS", "DatasetSpec", "EpsilonSpec", "JobSpec", "SweepSpec",
           "fingerprint", "registry_signature"]
