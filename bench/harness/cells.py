"""Everything a cell is made of, found by name under ``bench/``.

  BENCHMARK.json           the cells: name -> configuration, traffic, chips
  configs/<config>.json    the sweep as it is run: a full `SweepSpec` dict
                           plus ``source``, ``reduced``, ``assumed``
  traffic/<traffic>.json   how requests are issued (seed replicates, mesh)
  limits/<cell>.json       what the check compares, and each number's limit
  metrics/<metric>.py      one reader per per-layer metric: ``read(ctx)``
  flops/<algorithm>.py     operations one job of that algorithm requires
  flops/kernels/<k>.py     operations and bytes of one kernel call
  peaks.json               each device kind's published peaks

A later cell, metric or algorithm is a new file here; nothing is edited.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
from pathlib import Path
from typing import Dict

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

#: keys of a configuration file that describe it and are not spec fields
CONFIG_EXTRAS = ("source", "reduced", "assumed", "deployment")


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return _json(ROOT / "BENCHMARK.json")


def workload(name: str) -> Dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict:
    """The configuration file as written (spec fields and extras)."""
    return _json(BENCH / "configs" / f"{name}.json")


def spec_dict(cfg: Dict) -> Dict:
    """The `SweepSpec` fields of a configuration file."""
    return {k: v for k, v in cfg.items() if k not in CONFIG_EXTRAS}


def traffic(name: str) -> Dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def limits(cell: str) -> Dict:
    return _json(BENCH / "limits" / f"{cell}.json")


def peaks(device_kind: str) -> Dict:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; add them with their source")
    return table["devices"][device_kind]


def _module(path: Path):
    if not path.exists():
        raise KeyError(f"{path.relative_to(ROOT)} does not exist")
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))   # its sibling helpers
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _module(BENCH / "metrics" / f"{name}.py")


def algorithm_flops(algorithm: str):
    return _module(BENCH / "flops" / f"{algorithm}.py")


def kernel_counts(kernel: str):
    return _module(BENCH / "flops" / "kernels" / f"{kernel}.py")


def kernel_names():
    return sorted(p.stem for p in (BENCH / "flops" / "kernels").glob("*.py")
                  if not p.stem.startswith("_"))


# ---------------------------------------------------------------------------
# requests: the cell's spec with seeds drawn from --seed and the index
# ---------------------------------------------------------------------------

def derive_seed(seed: int, k: int, tag: str) -> int:
    """A generator seed in [0, 2**31) for request ``k`` of run ``seed``."""
    h = hashlib.sha256(f"{seed}:{k}:{tag}".encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


def request(cfg: Dict, tfc: Dict, seed: int, k: int) -> Dict:
    """Spec dict of the run's k-th sweep: every dataset seed and the split
    seed drawn anew, so no two requests of a run (or of two runs) are one
    sweep, and the traffic's seed replicates and mesh."""
    spec = json.loads(json.dumps(spec_dict(cfg)))
    for name, ds in spec["datasets"].items():
        ds["seed"] = derive_seed(seed, k, f"dataset:{name}")
    spec["split_seed"] = derive_seed(seed, k, "split")
    spec["n_seeds"] = tfc["n_seeds"]
    spec["devices"] = None
    return spec
