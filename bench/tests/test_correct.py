"""The check that decides ``correct`` fails where it must.

All run on the CPU at a shortened size (fewer iterations; datasets, grid
and jobs as configured):

* each control: the reference computed one precision step lower, in its
  products (three bfloat16 passes) or in its data (bfloat16), in the
  program's place;
* a whole run of the harness (its look for a chip skipped) with the timed
  path broken underneath, once for each fault a one-chip cell can have: a
  step that returns its state unchanged (in each algorithm), half of the
  batch left out with the mean over the rest, ECD-PSGD's compression made
  coarser, and an answer altered where it is produced;
* the readouts the reference derives (epsilon, costs, measured and
  predicted m_max) against the program's on a small Table II sweep.

On the chip the controls are read at each cell's own size by
``bench/calibrate.py``; PERF.md gives those readings.
"""

import json
import tempfile

import jax.numpy as jnp
import pytest

import run
from calibrate import as_program
from harness import cells, check, reference

ITERS = 60
CELLS = [w["name"] for w in cells.benchmark()["workloads"]]
CELL = "ls.seq"


def shortened(name):
    cfg = cells.config(cells.workload(name)["config"])
    cfg["iters"], cfg["eval_every"] = ITERS, ITERS // 10
    return cfg


@pytest.fixture
def short_cells(monkeypatch):
    real = cells.config

    def config(name):
        cfg = real(name)
        cfg["iters"], cfg["eval_every"] = ITERS, ITERS // 10
        return cfg
    monkeypatch.setattr(cells, "config", config)
    monkeypatch.setattr(cells, "peaks", lambda kind: {})
    monkeypatch.setattr(run, "PLATFORM", "cpu")


def drive(capsys, cell=CELL, seed=20241):
    assert run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", "0", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("control", reference.CONTROLS)
@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_the_reference_passes_itself(cell, control):
    w = cells.workload(cell)
    spec = cells.request(shortened(cell), cells.traffic(w["traffic"]), 5, 1)
    lim = cells.limits(cell)
    ref = reference.sweep(spec)
    assert check.compare(as_program(ref), ref, spec, lim)["ok"]
    ctl = check.compare(as_program(reference.sweep(spec, control=control)),
                        ref, spec, lim)
    assert not ctl["ok"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(short_cells, capsys, cell):
    line = drive(capsys, cell)
    assert line["correct"], line["check"]
    assert list(line)[-1] == "check"


@pytest.mark.parametrize("algorithm", ["minibatch.Minibatch",
                                       "ecd_psgd.EcdPsgd",
                                       "hogwild.Hogwild", "dadm.Dadm"])
def test_step_returning_its_state_unchanged(short_cells, capsys,
                                            monkeypatch, algorithm):
    import importlib
    module, name = algorithm.split(".")
    cls = getattr(importlib.import_module(
        f"repro.core.algorithms.{module}"), name)
    monkeypatch.setattr(cls, "step",
                        lambda self, prob, data, ctx, state, batch, t: state)
    assert not drive(capsys)["correct"]


def test_half_the_batch_left_out(short_cells, capsys, monkeypatch):
    from repro.core.problems import Problem

    def half(self, x, Xb, yb, active, mf):
        kept = active * (jnp.arange(active.shape[0])
                         < jnp.ceil(mf / 2)).astype(jnp.float32)
        c = self.dloss(Xb @ x, yb) * kept
        return (c @ Xb) / jnp.sum(kept) + self.lam * x
    monkeypatch.setattr(Problem, "masked_batch_grad", half)
    assert not drive(capsys)["correct"]


def test_ecd_compression_made_coarser(short_cells, capsys, monkeypatch):
    from repro.core.algorithms import ecd_psgd
    real = ecd_psgd.quantize_stochastic

    def four_bits(z, key, *, bits=8):
        return real(z, key, bits=4)
    monkeypatch.setattr(ecd_psgd, "quantize_stochastic", four_bits)
    assert not drive(capsys)["correct"]


def test_loss_altered_where_produced(short_cells, capsys, monkeypatch):
    from repro.experiments import engine
    real = engine._losses_dict

    def altered(*a, **kw):
        out = real(*a, **kw)
        out["losses"][0][-1] *= 1.001
        return out
    monkeypatch.setattr(engine, "_losses_dict", altered)
    assert not drive(capsys)["correct"]


def test_character_altered_where_produced(short_cells, capsys,
                                          monkeypatch):
    from repro.core import metrics
    real = metrics.summarize

    def altered(X, **kw):
        out = real(X, **kw)
        out["csim_async"] += 1.0
        return out
    monkeypatch.setattr(metrics, "summarize", altered)
    assert not drive(capsys)["correct"]


def test_readouts_agree_with_the_program():
    """Epsilon, costs and both m_max of a small Table II sweep (the
    registry's quick spec at 60 iterations), program against reference."""
    from repro.experiments import registry, runner
    from repro.experiments.spec import SweepSpec
    spec = registry.get_spec("upper_bound", quick=True).to_dict()
    spec["iters"], spec["eval_every"] = ITERS, ITERS // 10
    with tempfile.TemporaryDirectory() as d:
        prog = runner.run_sweep(SweepSpec.from_dict(spec), cache_dir=d)
    lim = {"tie": 1e-3, "limits": {"curve_gap": 3e-5, "epsilon_gap": 3e-5,
                                   "decisions_differ": 0,
                                   "characters_gap": 2e-5},
           "characters_not_compared": ["density"],
           "not_compared": [{"job": "ecd_psgd/dense"},
                            {"job": "hogwild/ub", "m_from": 8}]}
    ref = reference.sweep(spec)
    verdict = check.compare(prog, ref, spec, lim)
    assert verdict["ok"], verdict
    assert verdict["decisions"] > 0
    assert any("epsilon" in j for j in ref["jobs"].values())
    assert any("predicted_m_max" in j for j in ref["jobs"].values())
