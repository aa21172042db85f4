"""The work a sweep requires, from its spec alone: operations of every
job (bench/flops/<algorithm>.py) and the calls of the characters programs
(bench/flops/kernels/<program>.py)."""

from __future__ import annotations

from typing import Dict, List, Tuple

from harness import cells
from harness.reference import (BATCH, CHARACTER_ROWS, TAU_MAX, TRAIN_FRAC,
                               VALID_FRAC)


def sweep_flops(spec: Dict) -> int:
    """Operations of one sweep: every job, m and seed replicate, at the
    live worker count.  An algorithm with no count file is an error."""
    total = 0
    for job in spec["jobs"]:
        count = cells.algorithm_flops(job["algorithm"]).flops
        kw = spec["datasets"][job["dataset"]]["kwargs"]
        n, d = kw["n"], kw["d"]
        for m in spec["ms"]:
            total += spec.get("n_seeds", 1) * count(
                m, d, int(n * TRAIN_FRAC), int(n * VALID_FRAC),
                spec["iters"], spec["eval_every"], job.get("kwargs", {}))
    return total


def character_calls(spec: Dict) -> Dict[str, List[Tuple[int, ...]]]:
    """Calls of each characters program a sweep makes, per dataset: C_sim
    over the summary rows and, when the spec asks, over its own rows
    (``csim``: n, d, range), and the batch similarity (``pairwise_l0``:
    batches, batch, d)."""
    calls = {"csim": [], "pairwise_l0": []}
    for ds in spec["datasets"].values():
        n, d = ds["kwargs"]["n"], ds["kwargs"]["d"]
        rows = min(spec.get("characters_rows") or CHARACTER_ROWS, n)
        calls["csim"].append((rows, d, TAU_MAX))
        calls["pairwise_l0"].append((rows // BATCH, BATCH, d))
        if spec.get("measure_csim", 0) > 0:
            calls["csim"].append((min(spec["csim_rows"], n), d,
                                  spec["measure_csim"]))
    return calls
