"""Decide ``correct``: one sweep's answer against the plain reference.

Up to four numbers, each with a limit from ``limits/<cell>.json``:

  curve_gap         largest relative gap of a loss on the curves (every
                    compared job, worker count m, eval and seed replicate)
  epsilon_gap       largest relative gap of a job's epsilon readout
  decisions_differ  integer readouts that differ: the cost of each m, the
                    measured m_max, the predicted m_max.  A cost is
                    compared only where the reference's loss stays more
                    than ``tie`` (relative) away from epsilon at every eval
                    that decides it; a measured m_max only where all its
                    costs are.  An exact comparison: limit 0
  characters_gap    largest relative gap of a dataset character (the
                    Pallas C_sim / L0 kernels, variance, sparsity,
                    diversity, Thm-2 parameters)

Entries a last-ulp change turns chaotic are not compared; the limits file
names them (``not_compared``), and the characters that are not
(``characters_not_compared``: ``density``, which is one minus a float32
sparsity near 1 and so reads the rounding of that sparsity two hundred
times magnified); PERF.md gives the readings behind each.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def _skipped(rules: List[Dict], job: str, m: int, e: int) -> bool:
    for r in rules:
        if (r["job"] == job and m >= r.get("m_from", 0)
                and e >= r.get("eval_from", 0)):
            return True
    return False


def _rel(a, b) -> float:
    gap = abs(float(a) - float(b)) / max(abs(float(b)), 1e-12)
    return gap if np.isfinite(gap) else np.inf


def program_curves(job: Dict) -> np.ndarray:
    """(n_seeds, m, n_evals) from a `run_sweep` job result."""
    if "losses_seeds" in job:
        return np.asarray(job["losses_seeds"], np.float64).transpose(1, 0, 2)
    return np.asarray(job["losses"], np.float64)[None]


def _decisions(job_key: str, prog: Dict, ref: Dict, spec: Dict,
               rules: List[Dict], tie: float) -> Tuple[int, int]:
    """(compared, differing) integer readouts of one job."""
    compared = differ = 0
    if "predicted_m_max" in ref:
        compared += 1
        got = (prog.get("predicted") or {}).get("predicted_m_max")
        differ += got != ref["predicted_m_max"]
    if "costs" not in ref:
        return compared, differ
    eps, ms = ref["epsilon"], ref["ms"]
    curves = ref["losses_seeds"][0]
    probe = spec["epsilon"]["probe_m"]
    n_evals = curves.shape[1]
    eps_idx = min(int(n_evals * spec["epsilon"]["frac"]), n_evals - 1)
    robust = []
    for i, m in enumerate(ms):
        hits = np.nonzero(curves[i] <= eps)[0]
        last = hits[0] if len(hits) else n_evals - 1
        ok = not any(_skipped(rules, job_key, m, e) for e in range(last + 1))
        for e in range(last + 1):
            if m == probe and e == eps_idx:
                continue             # epsilon is this loss, on both sides
            ok &= abs(curves[i, e] - eps) > tie * abs(eps)
        robust.append(ok)
        if ok:
            compared += 1
            differ += prog["costs"][i] != ref["costs"][i]
    if all(robust):
        compared += 1
        differ += prog["measured_m_max"] != ref["measured_m_max"]
    return compared, differ


def compare(prog: Dict, ref: Dict, spec: Dict, lim: Dict) -> Dict:
    """The numbers compared, each ``{"value", "limit"}``, plus counts of
    what was compared.  ``prog`` is `run_sweep`'s result."""
    rules = lim.get("not_compared", [])
    curve_gap = eps_gap = 0.0
    entries = decisions = differ = 0
    missing = []
    for key, rj in ref["jobs"].items():
        pj = prog["jobs"].get(key)
        if pj is None or "losses" not in pj:
            missing.append(key)
            continue
        pc, rc = program_curves(pj), rj["losses_seeds"]
        if pc.shape != rc.shape:
            missing.append(key)
            continue
        for i, m in enumerate(rj["ms"]):
            for e in range(rc.shape[2]):
                if _skipped(rules, key, m, e):
                    continue
                gaps = np.abs(pc[:, i, e] - rc[:, i, e]) / np.maximum(
                    np.abs(rc[:, i, e]), 1e-12)
                curve_gap = max(curve_gap, float(np.nan_to_num(
                    gaps, nan=np.inf).max()))
                entries += rc.shape[0]
        if "epsilon" in rj and not any(r["job"] == key and "m_from" not in r
                                       for r in rules):
            if "epsilon" not in pj:
                missing.append(key)
                continue
            eps_gap = max(eps_gap, _rel(pj["epsilon"], rj["epsilon"]))
        c, d = _decisions(key, pj, rj, spec, rules, lim["tie"])
        decisions += c
        differ += d

    char_gap = 0.0
    for name, rd in ref["datasets"].items():
        pd = prog["datasets"].get(name)
        if pd is None:
            missing.append(name)
            continue
        pairs = [(pd.get("csim"), rd["csim"])] if "csim" in rd else []
        pairs += [(pd["characters"].get(k), v)
                  for k, v in rd["characters"].items()
                  if k not in lim.get("characters_not_compared", ())]
        for got, want in pairs:
            char_gap = max(char_gap, np.inf if got is None
                           else _rel(got, want))

    values = {"curve_gap": curve_gap, "epsilon_gap": eps_gap,
              "decisions_differ": differ, "characters_gap": char_gap}
    # a cell compares the numbers its limits file names: a sweep with no
    # epsilon readout or prediction has no epsilon_gap or decisions_differ
    numbers = {k: {"value": values[k], "limit": v}
               for k, v in lim["limits"].items()}
    ok = not missing and all(v["value"] <= v["limit"]
                             for v in numbers.values())
    return {"ok": bool(ok), "numbers": numbers, "missing": missing,
            "curve_entries": entries, "decisions": decisions}
