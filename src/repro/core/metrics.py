"""The paper's dataset-character indices (§IV).

  feature_variance   per-feature variance over the dataset (§IV.B)
  sparsity/density   fraction of zero elements (§IV.B)
  diversity          number of distinct sample kinds (§IV.C)
  C_sim_range        Eq. 3: windowed mean L0 distance along the sampling
                     sequence
  LS_A(D, S)         local similarity per algorithm class (§IV.A):
                       async (Hogwild!): C_sim_{tau_max} over the sequence
                       sync  (mini-batch/ECD-PSGD/DADM): the max over batches
                       of the batch-internal similarity

The hot paths (`csim`, `ls_sync`, `batch_internal_similarity`) are fused:
a single jitted `lax.scan` over the shift/pair range that routes the
per-row L0 count through the Pallas kernels in `repro.kernels.csim` when
``use_kernel`` is true, or through plain fused jnp otherwise.  The
default (``use_kernel=None``) picks the kernel route on TPU and the jnp
route elsewhere: off-TPU the kernels run in interpret mode, which is
emulation — correct (and test-covered) but slower than the fused jnp
scan.  The pure-jnp `*_ref` oracles — Python-loop `csim_ref`, broadcast
`batch_internal_similarity_ref`, per-batch `ls_sync_ref` — are retained
verbatim as the test references.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.telemetry import metrics as telemetry_metrics
from repro.telemetry import trace

#: bytes a host-side character pulls from the device, by call site
_HOST_FETCH_BYTES = telemetry_metrics.counter(
    "repro_host_fetch_bytes_total", labels={"site": "diversity"},
    help="bytes pulled from the device to the host, by call site")


def feature_mean(X):
    return jnp.mean(X, axis=0)


def feature_variance(X):
    """Per-feature variance (paper's 'feature variance_k')."""
    return jnp.var(X, axis=0)


def mean_feature_variance(X):
    return float(jnp.mean(feature_variance(X)))


def sparsity(X, tol=0.0):
    """Fraction of zero elements."""
    return float(jnp.mean(jnp.abs(X) <= tol))


def density(X, tol=0.0):
    return 1.0 - sparsity(X, tol)


def diversity(X, *, decimals=6):
    """Number of distinct sample kinds (exact row dedup).  The rows are
    pulled to the host for `np.unique`: a ``diversity`` span with their
    ``bytes``, counted in ``repro_host_fetch_bytes_total{site}``."""
    nbytes = int(np.prod(X.shape)) * np.dtype(X.dtype).itemsize
    _HOST_FETCH_BYTES.inc(nbytes)
    with trace.span("diversity", rows=int(X.shape[0]), bytes=nbytes):
        Xr = np.asarray(jax.device_get(X))
        Xr = np.round(Xr, decimals)
        return int(np.unique(Xr, axis=0).shape[0])


def diversity_ratio(X, **kw):
    return diversity(X, **kw) / X.shape[0]


# ---------------------------------------------------------------------------
# C_sim (Eq. 3) and LS_A
# ---------------------------------------------------------------------------

def l0_distance(a, b, tol=0.0):
    """||a - b||_0 — number of differing coordinates."""
    return jnp.sum((jnp.abs(a - b) > tol).astype(jnp.float32), axis=-1)


def csim_ref(X, rng: int, tol=0.0):
    """Eq. 3: C_sim_range = (1/n) sum_i (1/range) sum_{j=1..range}
    ||xi_i - xi_{(i+j) % n}||_0   (Python-unrolled pure-jnp oracle for the
    fused `csim` and the Pallas kernel)."""
    n = X.shape[0]
    total = jnp.zeros((), jnp.float32)
    for j in range(1, rng + 1):
        total = total + jnp.sum(l0_distance(X, jnp.roll(X, -j, axis=0), tol))
    return float(total / (n * rng))


@functools.partial(jax.jit, static_argnames=("rng", "tol"))
def _csim_scan(X, rng: int, tol):
    """Fused jnp Eq. 3: one `lax.scan` over the shift range.  The Pallas
    route is `repro.kernels.csim.csim_kernel` — the same scan with the
    per-shift L0 count done by the `l0_rows` kernel."""
    n = X.shape[0]
    rows = jnp.arange(n)

    def body(total, j):
        Xs = X[(rows + j) % n]               # == jnp.roll(X, -j, axis=0)
        return total + jnp.sum(l0_distance(X, Xs, tol)), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            jnp.arange(1, rng + 1))
    return total / (n * rng)


def _default_use_kernel() -> bool:
    # interpret-mode Pallas off-TPU is emulation: correct but slower than
    # the fused jnp scan, so the kernels are the default on TPU only
    return runtime.default_platform() == "tpu"


def csim(X, rng: int, tol=0.0, use_kernel=None):
    """Eq. 3, fused: a single jitted scan over the shift range.  With
    ``use_kernel`` (default: TPU only) the per-row L0 count runs through
    the Pallas kernel; otherwise fused jnp.  Oracle: :func:`csim_ref`."""
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        from repro.kernels import ops as kops
        return float(kops.csim(X, rng, tol))
    return float(_csim_scan(X, rng, tol))


@functools.partial(jax.jit, static_argnames=("tol", "use_kernel"))
def _pairwise_l0_means(batches, *, tol, use_kernel):
    """(nb, b, d) -> (nb,) mean pairwise L0 distance within each batch.

    Scans the b-1 in-batch cyclic shifts (shift s pairs row i with row
    (i+s) % b, covering every ordered pair exactly once) with the rows of
    all batches flattened, so each scan step is ONE (nb*b, d) L0 call —
    Pallas `l0_rows` or jnp — instead of nb separate (b, b, d) broadcasts
    with a host sync each.
    """
    nb, b, d = batches.shape
    flat = batches.reshape(nb * b, d)
    cols = jnp.arange(b)

    def body(tot, s):
        rolled = batches[:, (cols + s) % b, :].reshape(nb * b, d)
        if use_kernel:
            from repro.kernels import ops as kops
            dist = kops.l0_rows(flat, rolled, tol)
        else:
            dist = l0_distance(flat, rolled, tol)
        return tot + dist.reshape(nb, b).sum(axis=1), None

    tot, _ = jax.lax.scan(body, jnp.zeros((nb,), jnp.float32),
                          jnp.arange(1, b))
    return tot / (b * (b - 1) + 1e-9)


def batch_internal_similarity_ref(Xb, tol=0.0):
    """(b, b, d)-broadcast oracle for :func:`batch_internal_similarity`."""
    b = Xb.shape[0]
    diff = (jnp.abs(Xb[:, None, :] - Xb[None, :, :]) > tol)
    d = jnp.sum(diff.astype(jnp.float32), axis=-1)
    off = jnp.sum(d) - jnp.sum(jnp.diag(d))
    return float(off / (b * (b - 1) + 1e-9))


def batch_internal_similarity(Xb, tol=0.0, use_kernel=None):
    """Mean pairwise L0 distance within a batch — tractable proxy for the
    paper's 'max C_sim over orderings of the batch' (exact ordering search is
    a TSP; the mean pairwise distance brackets it and preserves ranking).

    Fused path: O(b d) memory shift-scan instead of the oracle's (b, b, d)
    broadcast.  Oracle: :func:`batch_internal_similarity_ref`.
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    return float(_pairwise_l0_means(Xb[None], tol=tol,
                                    use_kernel=use_kernel)[0])


def ls_async(X, tau_max: int, tol=0.0, use_kernel=None):
    """LS_A for asynchronous algorithms (Hogwild!): C_sim_{tau_max}."""
    return csim(X, tau_max, tol, use_kernel=use_kernel)


def ls_sync_ref(X, batch_size: int, tol=0.0):
    """Per-batch Python-loop oracle for :func:`ls_sync` (one device sync
    per batch)."""
    n = (X.shape[0] // batch_size) * batch_size
    batches = X[:n].reshape(-1, batch_size, X.shape[1])
    vals = [batch_internal_similarity_ref(batches[i])
            for i in range(batches.shape[0])]
    return float(max(vals))


def ls_auto(X, algorithm: str, window: int = 8, tol=0.0, use_kernel=None):
    """LS_A resolved through the Algorithm registry: asynchronous
    algorithms (Hogwild!) read C_sim over the sampling sequence with the
    window as tau_max, synchronous ones the max batch-internal similarity
    with the window as the batch size (§IV.A).  Works for any registered
    algorithm — the async/sync split is the class's `asynchronous` flag."""
    from repro.core.algorithms import base as alg_base
    if alg_base.get_algorithm(algorithm).asynchronous:
        return ls_async(X, window, tol, use_kernel=use_kernel)
    return ls_sync(X, window, tol, use_kernel=use_kernel)


def ls_sync(X, batch_size: int, tol=0.0, use_kernel=None):
    """LS_A for synchronous algorithms: max over batches of the batch's
    internal similarity.  Fused: every batch goes through one jitted
    shift-scan and the max reduces on device — a single host sync total.
    Oracle: :func:`ls_sync_ref`."""
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    n = (X.shape[0] // batch_size) * batch_size
    batches = X[:n].reshape(-1, batch_size, X.shape[1])
    return float(jnp.max(_pairwise_l0_means(batches, tol=tol,
                                            use_kernel=use_kernel)))


# ---------------------------------------------------------------------------
# Hogwild! theorem-2 parameters (Omega, delta, rho) from the dataset
# ---------------------------------------------------------------------------

def hogwild_params(X, tol=0.0):
    """Estimate (Omega, delta, rho) of Thm 2 for a *linear* model, where the
    gradient sparsity pattern equals the sample sparsity pattern.

      Omega: max #nonzeros in a sample
      delta: max frequency of any feature being nonzero
      rho:   max probability two random samples share a nonzero feature
    """
    nz = (jnp.abs(X) > tol).astype(jnp.float32)        # (n, d)
    omega = float(jnp.max(jnp.sum(nz, axis=1)))
    freq = jnp.mean(nz, axis=0)                        # (d,)
    delta = float(jnp.max(freq))
    # P(collision) <= sum_k freq_k^2  (union bound over features)
    rho = float(jnp.minimum(jnp.sum(freq * freq), 1.0))
    # omega_frac: support size as a fraction of d — the normalization that
    # makes Thm 2's "Omega delta^{1/2} extremely small" dimensionless
    return {"omega": omega, "omega_frac": omega / X.shape[1],
            "delta": delta, "rho": rho}


def summarize(X, *, tau_max=8, batch_size=8):
    """All paper indices in one report."""
    hw = hogwild_params(X)
    return {
        "n": int(X.shape[0]), "d": int(X.shape[1]),
        "mean_feature_variance": mean_feature_variance(X),
        "sparsity": sparsity(X),
        "density": density(X),
        "diversity": diversity(X),
        "diversity_ratio": diversity_ratio(X),
        "csim_async": ls_async(X, tau_max),
        "csim_sync": ls_sync(X, batch_size),
        **hw,
    }
