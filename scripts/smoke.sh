#!/usr/bin/env bash
# Smoke check: tier-1 tests + one engine sweep + the README quickstart
# commands as written.  ~10-15 min cold on CPU (sweeps are cached, so
# re-runs are much faster).  SMOKE_FULL=1 runs the whole tier-1 suite
# instead of the quick loop.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# report the device mesh this smoke runs on (CI's smoke-mesh8 job forces
# 8 host devices via XLA_FLAGS; sweeps then take the sharded engine path)
python -c "from repro.distributed import get_mesh; print(get_mesh().describe())"

echo "== [1/4] test suite (quick loop: -m 'not slow') =="
# The full tier-1 suite (ROADMAP.md) is `python -m pytest -x -q` with no
# marker filter; the smoke loop skips @pytest.mark.slow sweep/subprocess
# tests to stay under ~2 minutes on this CPU container.  SMOKE_FULL=1
# runs everything.
if [ "${SMOKE_FULL:-0}" = "1" ]; then
    python -m pytest -x -q
else
    python -m pytest -x -q -m "not slow"
fi

echo "== [2/4] sweep engine: registered specs =="
python -m repro.experiments.run --list

echo "== [3/4] sweep engine: Table II (upper_bound) quick =="
python -m repro.experiments.run --spec upper_bound --quick

echo "== [4/4] end-to-end paper study (quick) =="
python examples/paper_scalability_study.py

echo "smoke OK"
