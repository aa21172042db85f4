"""Plain reference for one sweep: what `run_sweep` has to answer, written
out directly and without any of the program's code.

Given the resolved sweep spec (the configuration's dict with the seeds of
one request), it builds each dataset from its seed, splits it, runs every
job once per worker count m at exactly m workers (no padding, no
bucketing, no masking), and derives the readouts and the dataset
characters.  The arithmetic follows the paper's definitions as the
configuration states them: float32 data, matrix products at full float32
precision ("highest").

``control`` computes the same thing one precision step below what the
configuration states, as a cheaper path would, for each of its two stated
precisions alone: ``"products"`` makes every matrix product three bfloat16
passes (the TPU's "high", emulated here so that it reads the same on any
device) on float32 data; ``"data"`` rounds each dataset to bfloat16 once
generated and keeps the products at "highest".

Random draws use `jax.random` with the keys the sweep's semantics fix:
dataset keys from each dataset's seed, the split from ``split_seed``, the
algorithm draws from key 0 (seed replicate s: ``fold_in(key0, s)``), made
at the top of the worker grid and read by member m in its first m columns.
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

LAMBDA = 0.01          # Eq. 4's L2 weight, the paper's lambda
TRAIN_FRAC, VALID_FRAC = 0.7, 0.2
CHARACTER_ROWS = 512   # rows of each dataset the characters read by default
TAU_MAX = BATCH = 8    # C_sim range and batch of the characters
PARALLEL_COST = 1e-3   # the predictors' per-worker parallel cost
M_CAP = 4096           # the predictors' search cap
ASYNCHRONOUS = {"hogwild"}   # cost divides server iterations by m


# ---------------------------------------------------------------------------
# matrix products at the stated precision, or one step below
# ---------------------------------------------------------------------------

def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


CONTROLS = ("products", "data")


def make_mm(control: str):
    """``mm(a, b)``: a @ b in float32, or as three bfloat16 passes."""
    hp = jax.lax.Precision.HIGHEST
    if control != "products":
        return lambda a, b: jnp.matmul(a, b, precision=hp)

    def mm3(a, b):
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)
        # each product of two bfloat16 values is exact in float32
        return (jnp.matmul(ah, bh, precision=hp)
                + jnp.matmul(ah, bl, precision=hp)
                + jnp.matmul(al, bh, precision=hp))
    return mm3


# ---------------------------------------------------------------------------
# datasets (paper Table I constructions, labels by the alternating ruler)
# ---------------------------------------------------------------------------

def _labels(X, mm):
    d = X.shape[1]
    r = jnp.arange(1, d + 1, dtype=jnp.float32)
    ruler = r * ((-1.0) ** r)
    y = jnp.sign(mm(X, ruler))
    return jnp.where(y == 0, 1.0, y)


def _masked_uniform(key, n, d, density, lo, hi):
    k1, k2 = jax.random.split(key)
    mask = jax.random.bernoulli(k1, density, (n, d))
    vals = jax.random.uniform(k2, (n, d), minval=lo, maxval=hi)
    return jnp.where(mask, vals, 0.0)


def _ls_sequence(key, n, d, mutate_frac, density=1.0, lo=-4.0, hi=3.0):
    keys = jax.random.split(key, 4)
    first = jax.random.uniform(keys[0], (d,), minval=lo, maxval=hi)
    if density < 1.0:
        first = jnp.where(jax.random.bernoulli(keys[1], density, (d,)),
                          first, 0.0)
    n_mut = max(1, int(mutate_frac * d))

    def step(x, k):
        k1, k2, k3 = jax.random.split(k, 3)
        idx = jax.random.choice(k1, d, (n_mut,), replace=False)
        x = x.at[idx].set(jax.random.uniform(k2, (n_mut,), minval=lo,
                                             maxval=hi))
        if density < 1.0:
            x = jnp.where(jax.random.bernoulli(k3, density, (d,)), x, 0.0)
        return x, x

    _, X = jax.lax.scan(step, first, jax.random.split(keys[2], n))
    return X


def make_dataset(ds: Dict, mm):
    """X, y of one dataset entry ``{"generator", "kwargs", "seed"}``."""
    key = jax.random.PRNGKey(ds["seed"])
    kw = dict(ds["kwargs"])
    gen = ds["generator"]
    if gen in ("upper_bound", "realsim_like"):
        X = _masked_uniform(key, kw["n"], kw["d"], kw["density"],
                            kw.get("lo", 0.0), kw.get("hi", 1.0))
    elif gen == "higgs_like":
        X = jax.random.uniform(key, (kw["n"], kw["d"]),
                               minval=kw.get("lo", -4.0),
                               maxval=kw.get("hi", 3.0))
    elif gen == "ls_sequence":
        X = _ls_sequence(key, **kw)
    else:
        raise KeyError(f"the reference has no generator {gen!r}")
    if ds.get("variant") is not None:
        raise KeyError("the reference builds no diversity variants")
    return X, _labels(X, mm)


def split(ds: Dict, X, y, split_seed: int):
    """70 % train, the next 20 % the held-out set the curves evaluate on."""
    n = X.shape[0]
    idx = (jax.random.permutation(jax.random.PRNGKey(split_seed), n)
           if ds.get("shuffle_split", True) else jnp.arange(n))
    ntr, nva = int(n * TRAIN_FRAC), int(n * VALID_FRAC)
    tr, va = idx[:ntr], idx[ntr:ntr + nva]
    return (X[tr], y[tr]), (X[va], y[va])


# ---------------------------------------------------------------------------
# the four algorithms, each at exactly m workers
# ---------------------------------------------------------------------------

_sig = jax.nn.sigmoid


def _point_grad(mm, x, xi, yi):
    """Gradient of log(1 + exp(-y xi.x)) + lam/2 |x|^2 at one sample."""
    return -_sig(-(yi * mm(xi, x))) * yi * xi + LAMBDA * x


def _test_loss(mm, x, X, y):
    return jnp.mean(jnp.logaddexp(0.0, -(y * mm(X, x))))


def _draws(alg: str, kw: Dict, key, n: int, iters: int, m_top: int):
    if alg == "hogwild":
        return jax.random.randint(key, (iters,), 0, n)
    if alg == "minibatch":
        return jax.random.randint(key, (iters, m_top), 0, n)
    if alg == "ecd_psgd":
        k_order, k_q = jax.random.split(key)
        order = jax.random.randint(k_order, (iters, m_top), 0, n)
        keys = jax.vmap(lambda t: jax.random.split(
            jax.random.fold_in(k_q, t), m_top))(jnp.arange(iters))
        return {"order": order, "keys": keys}
    if alg == "dadm":
        return jax.random.randint(
            key, (iters, m_top, kw.get("local_batch", 8)), 0, n)
    raise KeyError(f"the reference has no algorithm {alg!r}")


def _first_m(alg, draws, m):
    if alg == "hogwild":
        return draws
    if alg == "ecd_psgd":
        return {"order": draws["order"][:, :m], "keys": draws["keys"][:, :m]}
    return draws[:, :m]


def _algorithm(alg: str, kw: Dict, m: int, mm, X, y):
    """(state0, step(state, draw, t), model(state)) for m workers."""
    n, d = X.shape
    if alg == "hogwild":
        gamma = kw.get("gamma", 0.1)

        def step(state, i, j):
            x, hist = state
            tau = (j % m) + 1             # Thm 1: lag cycles over 1..m
            g = _point_grad(mm, hist[(j - tau) % m], X[i], y[i])
            x = x - gamma * g
            return x, hist.at[j % m].set(x)
        return (jnp.zeros(d), jnp.zeros((m, d))), step, lambda s: s[0]

    if alg == "minibatch":
        gamma = kw.get("gamma", 0.1)

        def step(x, idx, t):
            Xb, yb = X[idx], y[idx]
            c = -_sig(-(yb * mm(Xb, x))) * yb
            return x - gamma * (mm(c, Xb) / m + LAMBDA * x)
        return jnp.zeros(d), step, lambda x: x

    if alg == "ecd_psgd":
        gamma = kw.get("gamma", 0.1)
        qmax = 2.0 ** (kw.get("compress_bits", 8) - 1) - 1.0
        eye = jnp.eye(m)
        W = (eye + jnp.roll(eye, -1, axis=1) + jnp.roll(eye, 1, axis=1)) / 3.0

        def compress(z, k):
            scale = jnp.maximum(jnp.max(jnp.abs(z)), 1e-12) / qmax
            u = jax.random.uniform(k, z.shape, jnp.float32)
            q = jnp.clip(jnp.floor(z / scale + u), -qmax - 1, qmax)
            return q * scale

        def step(state, draw, t):
            xs, ys = state
            tf = t.astype(jnp.float32) + 1.0
            i = draw["order"]
            grads = jax.vmap(lambda xw, iw: _point_grad(mm, xw, X[iw], y[iw])
                             )(xs, i)
            x_new = mm(W, ys) - gamma * grads
            z = (1.0 - tf / 2.0) * xs + (tf / 2.0) * x_new
            cz = jax.vmap(compress)(z, draw["keys"])
            return x_new, (1.0 - 2.0 / tf) * ys + (2.0 / tf) * cz
        zeros = jnp.zeros((m, d))
        return (zeros, zeros), step, lambda s: jnp.mean(s[0], axis=0)

    if alg == "dadm":
        lam_n = LAMBDA * n
        sdca = jnp.minimum(1.0, lam_n / (jnp.sum(X * X, axis=1) / 4.0
                                         + lam_n))
        alpha0 = jnp.full((n,), 0.5)

        def step(state, idx, t):
            alpha, v = state

            def worker(iw):               # one worker's local batch
                Xi, yi = X[iw], y[iw]
                da = (_sig(-(yi * mm(Xi, v))) - alpha[iw]) * sdca[iw]
                return da, mm(yi * da, Xi) / lam_n
            das, dvs = jax.vmap(worker)(idx)
            alpha = alpha.at[idx.reshape(-1)].add(das.reshape(-1))
            return alpha, v + jnp.sum(dvs, axis=0)
        v0 = mm(y * alpha0, X) / lam_n
        return (alpha0, v0), step, lambda s: s[1]

    raise KeyError(f"the reference has no algorithm {alg!r}")


def _curve_fn(alg, kw, m, mm, iters, eval_every):
    n_evals = iters // eval_every

    def curve(Xtr, ytr, Xte, yte, draws):
        state0, step, model = _algorithm(alg, kw, m, mm, Xtr, ytr)
        ts = jnp.arange(n_evals * eval_every).reshape(n_evals, eval_every)
        blocks = jax.tree.map(
            lambda a: a[:n_evals * eval_every].reshape(
                (n_evals, eval_every) + a.shape[1:]), draws)

        def inner(state, inp):
            draw, t = inp
            return step(state, draw, t), None

        def outer(state, inp):
            block, tblock = inp
            state, _ = jax.lax.scan(inner, state, (block, tblock))
            return state, _test_loss(mm, model(state), Xte, yte)

        _, losses = jax.lax.scan(outer, state0, (blocks, ts))
        return losses

    # one curve per seed replicate: the seed axis leads the draws
    return jax.jit(jax.vmap(curve, in_axes=(None, None, None, None, 0)))


# ---------------------------------------------------------------------------
# readouts (paper §V.B) and predictions (Thms 2-4)
# ---------------------------------------------------------------------------

def epsilon(curves_by_m: Dict[int, List[float]], probe_m: int, frac: float):
    curve = curves_by_m[probe_m]
    return float(curve[min(int(len(curve) * frac), len(curve) - 1)])


def cost(curve, eval_every: int, eps: float, m: int, asynchronous: bool,
         iters: int):
    """Iterations each worker runs until the loss first reaches eps."""
    hits = [i for i, v in enumerate(curve) if v <= eps]
    if not hits:
        return float(iters)
    it = float((hits[0] + 1) * eval_every)
    return it / m if asynchronous else it


def measured_mmax(ms, costs):
    for i in range(len(ms) - 1):
        if costs[i] - costs[i + 1] <= 0.0:
            return ms[i]
    return ms[-1]


def hogwild_params(X: np.ndarray):
    nz = (np.abs(X) > 0).astype(np.float64)
    freq = nz.mean(axis=0)
    omega = float(nz.sum(axis=1).max())
    return {"omega": omega, "omega_frac": omega / X.shape[1],
            "delta": float(freq.max()),
            "rho": float(min((freq * freq).sum(), 1.0))}


def diversity(X: np.ndarray) -> int:
    return int(np.unique(np.round(X, 6), axis=0).shape[0])


def predicted_mmax(alg: str, X: np.ndarray) -> int:
    if alg == "hogwild":
        p = hogwild_params(X)
        term = p["omega_frac"] * math.sqrt(p["delta"])
        c1 = 1.0 + 6.0 * p["rho"] + 6.0 * term
        for m in range(2, M_CAP + 1):
            if 1.0 / m + 6.0 * p["rho"] + 6.0 * m * term >= c1:
                return m - 1
        return M_CAP
    if alg == "dadm":
        def gain(m):
            return diversity(X) / X.shape[0] * (1.0 / m - 1.0 / (m + 1))
    else:                      # synchronous SGD (Thm 3/4)
        sigma = math.sqrt(max(float(X.var(axis=0).mean()), 1e-12))

        def gain(m):
            return sigma * (1.0 / math.sqrt(m) - 1.0 / math.sqrt(m + 1))
    for m in range(1, M_CAP):
        if gain(m) <= PARALLEL_COST:
            return m
    return M_CAP


# ---------------------------------------------------------------------------
# dataset characters (§IV)
# ---------------------------------------------------------------------------

def csim(X: np.ndarray, rng: int) -> float:
    """Eq. 3: mean L0 distance between each row and its next ``rng`` rows
    along the sampling order (cyclic)."""
    n = X.shape[0]
    total = sum(int((X != np.roll(X, -j, axis=0)).sum())
                for j in range(1, rng + 1))
    return total / (n * rng)


def batch_similarity(X: np.ndarray, b: int) -> float:
    """Largest mean pairwise L0 distance inside consecutive batches of b."""
    n = (X.shape[0] // b) * b
    best = 0.0
    for s in range(0, n, b):
        B = X[s:s + b]
        pair = (B[:, None, :] != B[None, :, :]).sum()
        best = max(best, pair / (b * (b - 1) + 1e-9))
    return best


def characters(X: np.ndarray) -> Dict:
    sparsity = float((np.abs(X) <= 0).mean())
    div = diversity(X)
    return {"n": X.shape[0], "d": X.shape[1],
            "mean_feature_variance": float(X.astype(np.float64)
                                           .var(axis=0).mean()),
            "sparsity": sparsity, "density": 1.0 - sparsity,
            "diversity": div, "diversity_ratio": div / X.shape[0],
            "csim_async": csim(X, TAU_MAX),
            "csim_sync": batch_similarity(X, BATCH),
            **hogwild_params(X)}


# ---------------------------------------------------------------------------
# the whole sweep
# ---------------------------------------------------------------------------

def sweep(spec: Dict, *, control: str = "") -> Dict:
    """What `run_sweep` should return for ``spec`` (a resolved
    `SweepSpec` dict): curves per job (seed replicates ``(n_seeds, m,
    n_evals)``), readouts, predictions and dataset characters.  ``control``
    is ``""`` or one of `CONTROLS`."""
    if control and control not in CONTROLS:
        raise ValueError(f"no control {control!r}; one of {CONTROLS}")
    mm = make_mm(control)
    with jax.default_matmul_precision("highest"):
        data = {}
        for name, ds in spec["datasets"].items():
            X, y = make_dataset(ds, mm)
            if control == "data":
                X = X.astype(jnp.bfloat16).astype(jnp.float32)
            data[name] = (X, y, split(ds, X, y, spec["split_seed"]))

        out = {"datasets": {}, "jobs": {}}
        for name, (X, _, _) in data.items():
            Xh = np.asarray(jax.device_get(X))
            info = {"n": Xh.shape[0], "d": Xh.shape[1]}
            if spec.get("measure_csim", 0) > 0:
                info["csim"] = csim(Xh[:spec["csim_rows"]],
                                    spec["measure_csim"])
            rows = spec.get("characters_rows") or CHARACTER_ROWS
            info["characters"] = characters(Xh[:rows])
            out["datasets"][name] = info

        ms, iters, every = list(spec["ms"]), spec["iters"], spec["eval_every"]
        key0 = jax.random.PRNGKey(0)
        seed_keys = [key0] + [jax.random.fold_in(key0, s)
                              for s in range(1, spec.get("n_seeds", 1))]
        for job in spec["jobs"]:
            alg, kw = job["algorithm"], job.get("kwargs", {})
            if job.get("problem", "logistic") != "logistic":
                raise KeyError("the reference runs the logistic loss only")
            X, y, ((Xtr, ytr), (Xte, yte)) = data[job["dataset"]]
            draws = [_draws(alg, kw, k, Xtr.shape[0], iters, max(ms))
                     for k in seed_keys]
            curves = []
            for m in ms:
                stacked = jax.tree.map(lambda *a: jnp.stack(a),
                                       *[_first_m(alg, d, m) for d in draws])
                fn = _curve_fn(alg, kw, m, mm, iters, every)
                curves.append(np.asarray(fn(Xtr, ytr, Xte, yte, stacked)))
            seeds = np.stack(curves, axis=1)      # (n_seeds, m, n_evals)
            jr = {"ms": ms, "losses_seeds": seeds.astype(np.float64)}
            if spec.get("epsilon") is not None:
                by_m = {m: list(seeds[0, i]) for i, m in enumerate(ms)}
                eps = epsilon(by_m, spec["epsilon"]["probe_m"],
                              spec["epsilon"]["frac"])
                costs = [cost(by_m[m], every, eps, m, alg in ASYNCHRONOUS,
                              iters) for m in ms]
                jr.update(epsilon=eps, costs=costs,
                          measured_m_max=measured_mmax(ms, costs))
            if job.get("predict"):
                Xp = np.asarray(jax.device_get(X))
                if job.get("predict_rows", 0) > 0:
                    Xp = Xp[:job["predict_rows"]]
                jr["predicted_m_max"] = predicted_mmax(alg, Xp)
            key = job["algorithm"] + "/" + job["dataset"]
            out["jobs"][key] = jr
    return out
