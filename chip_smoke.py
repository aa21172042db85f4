#!/usr/bin/env python3
"""Smoke check of the system's main path on a TPU, through its entry points.

  python chip_smoke.py              # one chip: phases A-D
  python chip_smoke.py --chips 4    # four chips: sharded sweep + racing Hogwild!

One chip:
  A  sweep      `run_sweep` of the full ``upper_bound`` spec on the chip,
                against the same spec run sequentially on the host CPU
  B  characters C_sim and LS_sync on the ``ub`` dataset through the
                compiled Pallas kernels, against the fused jnp route
  C  probe      one analytic advisor probe through the batched slot path,
                against the sequential advisor on the host CPU
  D  model      3 training steps of gemma3-1b at published widths (batch
                8 x seq 64), step 0's loss against a float32 forward of the
                same weights and batch on the host CPU
Four chips (``--chips 4``, and nothing else):
  M  mesh       ``upper_bound`` sharded over 4 chips against 1 chip
  R  race       racing Hogwild! under shard_map (m=4, sync_every=1) against
                its sequential oracle

Each phase prints one line with its compile seconds (set-up, not a
benchmark number) and its gap to the reference.  Any failure exits
non-zero.  The script exits non-zero and prints no result when JAX finds
no TPU.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
Weights and data come from ``--seed`` and the specs' own seeds; the
artifact cache under ``results/`` is never read or written.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import runtime  # noqa: E402

#: the platform every phase must run on (rehearsals on the CPU change it)
PLATFORM = "tpu"

#: phase A: relative gap allowed between the chip's and the host CPU's
#: losses, per job, at the first and the last eval (None: reported, not
#: bounded).  The runner computes sweeps at matmul precision "highest";
#: so computed, a v5e's stable jobs differed by at most 1.4e-5 (at the
#: TPU's default single bf16 pass: 2.2e-3).  Chaotic trajectories grow any
#: last-ulp change to O(1) (docs/distributed.md): Hogwild! on ``ub`` from
#: m = 8 on by the last eval, ECD-PSGD's stochastic rounding from the
#: first (its vmapped and sequential CPU runs already differ by 1.6e-2)
SWEEP_RTOL = {"hogwild/ub": (1e-4, None),
              "minibatch/dense": (1e-4, 1e-4),
              "ecd_psgd/dense": (None, None),
              "dadm/sparse8": (1e-4, 1e-4)}
CHAOTIC = tuple(k for k, (_, last) in SWEEP_RTOL.items() if last is None)

#: phase D: relative gap allowed between step 0's loss on the chip (bf16
#: weights and activations) and the float32 host forward
LOSS_RTOL = 1e-2

#: phases M and R: the mesh-invariance contract (docs/distributed.md),
#: from which the chaotic jobs are exempt
MESH_ATOL = 1e-5

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class PhaseFailed(RuntimeError):
    pass


@contextlib.contextmanager
def compile_clock():
    """Seconds spent in XLA compiles (persistent-cache reads included) and
    the persistent-cache hits, while the block runs."""
    got = {"compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == _COMPILE_EVENT:
            got["compile_s"] += duration

    def on_event(event, **_):
        if event == _CACHE_HIT_EVENT:
            got["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    t0 = time.perf_counter()
    try:
        yield got
    finally:
        got["wall_s"] = time.perf_counter() - t0
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def report(phase, what, clock, gap, ok, **extra):
    status = "PASS" if ok else "FAIL"
    fields = " ".join(f"{k}={v}" for k, v in extra.items())
    print(f"[{phase}] {status} {what}: compile_s={clock['compile_s']!r} "
          f"cache_hits={clock['cache_hits']} wall_s={clock['wall_s']!r} "
          f"gap={gap} {fields}".rstrip(), flush=True)
    if not ok:
        raise PhaseFailed(f"phase {phase} failed: {what}")


def host_cpu():
    return jax.default_device(jax.devices("cpu")[0])


def _rel_gap(job, ref_job, col):
    mine = np.asarray(job["losses"], np.float64)[:, col]
    theirs = np.asarray(ref_job["losses"], np.float64)[:, col]
    return np.abs(mine - theirs) / np.maximum(np.abs(theirs), 1e-12), theirs


def phase_sweep(spec):
    """A: the paper's sweep on the chip against the sequential CPU run."""
    from repro.experiments import runner
    from repro.experiments import spec as spec_mod

    with contextlib.redirect_stdout(sys.stderr), compile_clock() as clock:
        res = runner.run_sweep(spec, use_cache=False)
    with contextlib.redirect_stdout(sys.stderr), host_cpu():
        ref = runner.run_sweep(spec, use_cache=False, use_vmap=False)
        X_host = {n: np.asarray(spec_mod.build_dataset(d).X)
                  for n, d in spec.datasets.items()}
    X_chip = {n: np.asarray(spec_mod.build_dataset(d).X)
              for n, d in spec.datasets.items()}

    ok = (res["execution"]["backend"] == PLATFORM
          and ref["execution"]["backend"] == "cpu")
    first, final, seps = {}, {}, {}
    for key, job in res["jobs"].items():
        finite = np.isfinite(np.asarray(job.get("losses", np.nan))).all()
        ok &= job["status"] == "ok" and bool(finite)
        if job["status"] != "ok":
            continue
        first[key] = float(_rel_gap(job, ref["jobs"][key], 0)[0].max())
        rel, theirs = _rel_gap(job, ref["jobs"][key], -1)
        final[key] = float(rel.max())
        # what separates adjacent m in the reference, relative
        seps[key] = float((np.abs(np.diff(theirs))
                           / np.maximum(np.abs(theirs[:-1]), 1e-12)).min())
        for gap, bound in zip((first[key], final[key]), SWEEP_RTOL[key]):
            ok &= bound is None or gap <= bound
    data_gap = {n: float(np.abs(X_chip[n] - X_host[n]).max())
                for n in X_chip}
    report("A", f"run_sweep({spec.name}, {len(spec.jobs)} jobs, "
                f"iters={spec.iters}, ms={list(spec.ms)})", clock,
           json.dumps(final), ok,
           first_eval_gap=json.dumps(first),
           backend=res["execution"]["backend"],
           statuses=",".join(j["status"] for j in res["jobs"].values()),
           adjacent_m_sep=json.dumps(seps),
           dataset_max_abs_diff=json.dumps(data_gap),
           chaotic=",".join(CHAOTIC))


def phase_characters(spec, tau=8, batch=8):
    """B: Pallas character kernels against the fused jnp route, exactly."""
    from repro.core import metrics as MX
    from repro.experiments import spec as spec_mod
    from repro.kernels import ops

    X = spec_mod.build_dataset(spec.datasets["ub"]).X
    with compile_clock() as clock:
        kernel = (MX.csim(X, tau, use_kernel=True),
                  MX.ls_sync(X, batch, use_kernel=True))
        plain = (MX.csim(X, tau, use_kernel=False),
                 MX.ls_sync(X, batch, use_kernel=False))
        n = (X.shape[0] // batch) * batch
        batches = X[:n].reshape(-1, batch, X.shape[1])
        hlo = (jax.jit(ops.csim, static_argnums=1).lower(X, tau)
               .compile().as_text()
               + MX._pairwise_l0_means.lower(batches, tol=0.0,
                                             use_kernel=True)
               .compile().as_text())
    custom = hlo.count("tpu_custom_call")
    ok = kernel == plain and (custom > 0 or PLATFORM != "tpu")
    report("B", f"csim(ub {X.shape[0]}x{X.shape[1]}, range {tau}) + "
                f"ls_sync(batch {batch}), Pallas vs jnp", clock,
           max(abs(a - b) for a, b in zip(kernel, plain)), ok,
           csim=repr(kernel[0]), ls_sync=repr(kernel[1]),
           tpu_custom_calls=custom)


def phase_probe(n=4000, d=28):
    """C: one analytic advisor probe through the batched slot path."""
    from repro.core.advisor import ScalabilityAdvisor
    from repro.experiments.spec import DatasetSpec
    from repro.service.api import AdvisorService, ProbeRequest

    req = ProbeRequest(dataset=DatasetSpec("higgs_like", {"n": n, "d": d}),
                       algorithm="hogwild", escalate=False)
    with tempfile.TemporaryDirectory() as empty_cache:
        svc = AdvisorService(cache_dir=empty_cache)
        with compile_clock() as clock:
            resp = svc.probe(req)
        with host_cpu():
            seq = ScalabilityAdvisor().from_dataset(
                req.materialize_X(svc.characters_rows))
    keys = ("mean_feature_variance", "sparsity", "omega_frac", "delta",
            "rho")
    gap = max(abs(resp.report[k] - seq[k]) / max(abs(seq[k]), 1e-12)
              for k in keys)
    strategies = ("hogwild", "sync", "dadm")
    mmax = {s: resp.report[s]["predicted_m_max"] for s in strategies}
    ref_mmax = {s: seq[s]["predicted_m_max"] for s in strategies}
    ok = resp.status == "ok" and resp.tier == "analytic"
    report("C", f"AdvisorService.probe(higgs_like {n}x{d})", clock,
           repr(gap), ok, status=resp.status, tier=resp.tier,
           m_max=json.dumps(mmax), host_m_max=json.dumps(ref_mmax))


def phase_model(cfg, seed, batch=8, seq_len=64, steps=3):
    """D: a model trained on the chip; step 0's loss against a float32
    host forward of the same weights and batch."""
    from repro.data.lm import LMConfig, hmm_stream
    from repro.launch.train import train_loop
    from repro.models import model as M

    key = jax.random.PRNGKey(seed)
    # train_loop draws its weights and batches from the same key; keep a
    # host copy of both (its weights are donated to the update)
    params = jax.device_get(M.init_params(key, cfg))
    first = jax.device_get(next(hmm_stream(
        key, LMConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                      batch_size=batch), 1)))
    with contextlib.redirect_stdout(sys.stderr), compile_clock() as clock:
        _, history = train_loop(cfg, steps=steps, batch_size=batch,
                                seq_len=seq_len, key=key, log_every=1)
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with host_cpu(), jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        ref = float(jax.jit(lambda p, b: M.loss_fn(p, cfg32, b)[0])(
            p32, jax.tree.map(jnp.asarray, first)))
    rel = abs(history[0] - ref) / abs(ref)
    ok = bool(np.isfinite(history).all()) and rel <= LOSS_RTOL
    report("D", f"train_loop({cfg.name}, batch {batch} x seq "
                f"{seq_len}, {steps} steps)", clock, repr(rel), ok,
           layers=cfg.num_layers, d_model=cfg.d_model,
           vocab=cfg.vocab_size, losses=json.dumps(history),
           host_f32_loss=repr(ref), peak_bytes_in_use=peak)


@contextlib.contextmanager
def placements_recorded():
    """Note, for every dispatch the partitioner makes, where each shard of
    each argument lives: ``(span, ((device id, shard shape), ...))``."""
    from repro.telemetry import instrument

    placements = []
    real = instrument.dispatch

    def spy(jfn, *args, span_name="bucket", **attrs):
        for a in args:
            if isinstance(a, jax.Array):
                placements.append((span_name, tuple(
                    (s.device.id, s.data.shape)
                    for s in a.addressable_shards)))
        return real(jfn, *args, span_name=span_name, **attrs)

    instrument.dispatch = spy
    try:
        yield placements
    finally:
        instrument.dispatch = real


def phase_mesh(spec, n_dev=4):
    """M: the sweep sharded over ``n_dev`` chips against one chip."""
    from repro.experiments import runner

    with contextlib.redirect_stdout(sys.stderr), \
            placements_recorded() as placed, compile_clock() as clock:
        sharded = runner.run_sweep(spec, use_cache=False, mesh=n_dev)
    with contextlib.redirect_stdout(sys.stderr):
        single = runner.run_sweep(spec, use_cache=False, mesh=1)
    gaps = {}
    for key, job in sharded["jobs"].items():
        a = np.asarray(job["losses"])
        b = np.asarray(single["jobs"][key]["losses"])
        gaps[key] = float(np.abs(a - b).max())
    # every sharded input is split evenly, one shard on each chip
    shards = [p for name, p in placed if name == "mesh_bucket"]
    spread = all(sorted(d for d, _ in p) == list(range(n_dev))
                 and len({shape for _, shape in p}) == 1 for p in shards)
    ok = (sharded["execution"]["devices"] == n_dev
          and sharded["execution"]["sharded"] and shards and spread
          and all(j["status"] == "ok" for j in sharded["jobs"].values())
          and all(g <= MESH_ATOL for k, g in gaps.items()
                  if k not in CHAOTIC))
    report("M", f"run_sweep({spec.name}, mesh={n_dev}) vs mesh=1", clock,
           json.dumps(gaps), ok, execution=json.dumps(sharded["execution"]),
           sharded_inputs=len(shards),
           first_input_shards=json.dumps(shards[:1]),
           chaotic=",".join(CHAOTIC))


def phase_race(spec, seed, n_dev=4, iters=3000, eval_every=300):
    """R: racing Hogwild! over ``n_dev`` chips against its oracle."""
    from repro.core.algorithms import run_hogwild
    from repro.distributed import run_hogwild_sharded
    from repro.experiments import spec as spec_mod

    ds = spec.datasets["dense"]
    tr, te = spec_mod.split_dataset(ds, spec_mod.build_dataset(ds),
                                    spec.split_seed)
    kw = dict(m=n_dev, iters=iters, gamma=0.05, eval_every=eval_every,
              key=jax.random.PRNGKey(seed))
    with compile_clock() as clock:
        race = run_hogwild_sharded(tr, te, sync_every=1, mesh=n_dev, **kw)
    oracle = run_hogwild(tr, te, **kw)
    gap = float(np.abs(np.asarray(race["losses"])
                       - np.asarray(oracle["losses"])).max())
    x_devices = sorted(d.id for d in race["x"].devices())
    ok = (race["devices"] == n_dev and gap <= MESH_ATOL
          and x_devices == list(range(n_dev)))
    report("R", f"run_hogwild_sharded(m={n_dev}, sync_every=1, "
                f"mesh={n_dev}) vs run_hogwild(m={n_dev})", clock,
           repr(gap), ok, psum_rounds=race["psum_rounds"],
           x_devices=json.dumps(x_devices),
           x_sharding=str(race["x"].sharding).replace(" ", ""))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-D on one chip; 4: only the "
                         "four-chip phases (sharded sweep, race)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the model weights and the race's draws")
    args = ap.parse_args(argv)
    cache_dir = runtime.enable_compile_cache()

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    if dev.platform != PLATFORM:
        print(f"no {PLATFORM.upper()} found (JAX default platform is "
              f"{dev.platform!r}); this check never falls back",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs.registry import get_arch
    from repro.experiments import registry
    spec = registry.get_spec("upper_bound")
    if args.chips == 1:
        phases = [lambda: phase_sweep(spec),
                  lambda: phase_characters(spec),
                  phase_probe,
                  lambda: phase_model(get_arch("gemma3-1b"), args.seed)]
    else:
        phases = [lambda: phase_mesh(spec, args.chips),
                  lambda: phase_race(spec, args.seed, args.chips)]
    failed = 0
    for run in phases:
        # a failed phase is reported and the rest still run, so one chip
        # call shows every fault; any failure fails the script
        try:
            run()
        except PhaseFailed as e:
            print(e, file=sys.stderr)
            failed += 1
        except Exception:  # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            failed += 1
    if failed:
        print(f"{failed} phase(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
