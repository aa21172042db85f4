import os
import sys

# Tests run on the single real CPU device (the 512-device override is
# dryrun.py-only, per the project contract).  A couple of mesh tests want a
# few virtual devices — they use their own subprocess.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", False)

import pytest


@pytest.fixture
def cold_programs():
    """Drop the engine's kept programs, so the test's first sweep traces,
    lowers and compiles every bucket program afresh."""
    from repro.experiments import engine
    engine.clear_programs()
