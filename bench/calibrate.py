#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

  python bench/calibrate.py --workload ls.seq --seeds 12 --out FILE

For each seed: one request of the cell, as the benchmark issues it,
through `run_sweep`; the plain reference of the same request; and each
control (the reference one precision step lower in its products, or in its
data: `reference.CONTROLS`).  Prints and writes, per seed, the compared
numbers for the program and for each control, and the per-entry curve gaps
(largest over seed replicates) of each, which show where a job's curve
turns chaotic.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

from harness import cells, check, reference  # noqa: E402


def entry_gaps(prog: dict, ref: dict) -> dict:
    out = {}
    for key, rj in ref["jobs"].items():
        pc = check.program_curves(prog["jobs"][key])
        rc = rj["losses_seeds"]
        gap = abs(pc - rc) / abs(rc).clip(1e-12)
        out[key] = gap.max(axis=0).tolist()
    return out


def as_program(ref: dict) -> dict:
    """The reference's answer in `run_sweep`'s result layout."""
    jobs = {}
    for key, rj in ref["jobs"].items():
        seeds = rj["losses_seeds"]
        j = {"losses": seeds[0].tolist(),
             "losses_seeds": seeds.transpose(1, 0, 2).tolist()}
        for k in ("epsilon", "costs", "measured_m_max"):
            if k in rj:
                j[k] = rj[k]
        if "predicted_m_max" in rj:
            j["predicted"] = {"predicted_m_max": rj["predicted_m_max"]}
        jobs[key] = j
    return {"jobs": jobs, "datasets": ref["datasets"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    from repro import runtime
    from repro.experiments import runner
    from repro.experiments.spec import SweepSpec
    runtime.enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)

    w = cells.workload(args.workload)
    cfg, tfc = cells.config(w["config"]), cells.traffic(w["traffic"])
    try:
        lim = cells.limits(args.workload)
    except FileNotFoundError:
        lim = {"not_compared": [], "tie": 1e-3}
    # raw readings: every limit open, so each number is reported as read
    lim = dict(lim, limits={"curve_gap": float("inf"),
                            "epsilon_gap": float("inf"),
                            "decisions_differ": float("inf"),
                            "characters_gap": float("inf")})
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        spec = cells.request(cfg, tfc, seed, 1)
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory() as d, \
                contextlib.redirect_stdout(sys.stderr):
            prog = runner.run_sweep(SweepSpec.from_dict(spec), cache_dir=d,
                                    mesh=tfc["mesh"])
        t1 = time.perf_counter()
        ref = reference.sweep(spec)
        t2 = time.perf_counter()
        row = {"seed": seed, "sweep_s": t1 - t0, "reference_s": t2 - t1,
               "statuses": {k: j["status"] for k, j in prog["jobs"].items()}}
        sides = {"program": prog}
        for name in reference.CONTROLS:
            t3 = time.perf_counter()
            sides[f"control_{name}"] = as_program(
                reference.sweep(spec, control=name))
            row[f"control_{name}_s"] = time.perf_counter() - t3
        for side, answer in sides.items():
            row[side] = {k: v["value"] for k, v in check.compare(
                answer, ref, spec, lim)["numbers"].items()}
            row[f"{side}_entries"] = entry_gaps(answer, ref)
        rows.append(row)
        print(json.dumps({k: row[k] for k in row
                          if not k.endswith("entries")}), flush=True)
        for side in sides:
            for job, gaps in row[f"{side}_entries"].items():
                worst = [max(g) for g in gaps]
                print(f"  {side} {job}: largest gap per m "
                      + " ".join(f"{v:.1e}" for v in worst)
                      + "; per eval at the largest m "
                      + " ".join(f"{v:.0e}" for v in gaps[-1]), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
