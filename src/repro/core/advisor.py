"""ScalabilityAdvisor — the paper's contribution as a first-class framework
feature: measure the dataset/gradient characters the trainer actually sees
and report the predicted scalability envelope next to the measured curve.

Production usage (any of the 10 archs):
    advisor = ScalabilityAdvisor()
    report = advisor.from_grads(per_shard_grads)    # gradient-level characters
    report = advisor.from_dataset(X, ...)           # raw-dataset characters
Both return {characters..., predicted m_max per strategy, recommendation}.
Invalid probes (empty/single-element shard lists, non-finite values,
too-small datasets) return a structured low-confidence report
(``valid: False`` + ``reason``) instead of NaN characters or a raise —
`repro.service` turns those into graceful API responses.

The m_max searches go through the vectorized scaling-law predictors in
`repro.analysis.fit` (one array scan over the m grid) rather than the
``while m < 4096`` Python loops of `repro.core.scalability` — those stay
as the scalar oracles, and tests/test_analysis.py pins the two paths to
identical answers.

Batched probes: :func:`masked_dataset_characters` and
:func:`masked_grad_characters` are the slots-batched twins of the scalar
character measurements — pure jnp over a padded ``(n_slots, ...)`` batch
with row/column validity masks, so `repro.service.batcher` can answer N
concurrent probes with ONE vmapped-style jitted call (pad-to-slot, the
same masked-batch idiom the sweep engine and `serve.SlotDriver` use)
instead of N sequential `from_dataset`/`from_grads` calls.  Padded rows/
columns/slots are exact no-ops: every reduction is mask-weighted, so the
batched characters match the sequential ones (pinned <= 1e-6 in
tests/test_service.py).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.analysis import fit as FIT
from repro.core import metrics as MX


def _flatten(tree):
    return jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                            for x in jax.tree.leaves(tree)])


# ---------------------------------------------------------------------------
# masked (slots-batched) character kernels — the service's batched path
# ---------------------------------------------------------------------------

def masked_dataset_characters(Xs, row_mask, col_mask) -> Dict:
    """Slots-batched §IV dataset characters under validity masks.

    ``Xs``: ``(n_slots, R, D)`` zero-padded datasets; ``row_mask``
    ``(n_slots, R)`` and ``col_mask`` ``(n_slots, D)`` are 1.0 on real
    rows/columns.  Returns ``(n_slots,)`` arrays for every maskable
    character (variance, sparsity, density, the Thm-2 Hogwild! params);
    `diversity` needs an exact row dedup and is counted per slot by
    `core.metrics.diversity` (see
    `ScalabilityAdvisor.dataset_characters_batch`).  All-padding
    slots (inactive batch slots) produce zeros, never NaN."""
    rm = row_mask[:, :, None]                        # (s, R, 1)
    cm = col_mask[:, None, :]                        # (s, 1, D)
    cell = rm * cm                                   # (s, R, D)
    n = jnp.sum(row_mask, axis=1)                    # (s,)
    d = jnp.sum(col_mask, axis=1)                    # (s,)
    n_safe = jnp.maximum(n, 1.0)
    d_safe = jnp.maximum(d, 1.0)

    mean = jnp.sum(Xs * cell, axis=1) / n_safe[:, None]          # (s, D)
    var_k = jnp.sum(((Xs - mean[:, None, :]) * rm) ** 2 * cm,
                    axis=1) / n_safe[:, None]                    # (s, D)
    mean_feature_variance = jnp.sum(var_k * col_mask,
                                    axis=1) / d_safe
    zeros = (jnp.abs(Xs) <= 0.0).astype(jnp.float32) * cell
    sparsity = jnp.sum(zeros, axis=(1, 2)) / (n_safe * d_safe)

    nz = (jnp.abs(Xs) > 0.0).astype(jnp.float32) * cell          # (s, R, D)
    omega = jnp.max(jnp.sum(nz, axis=2), axis=1)                 # (s,)
    freq = jnp.sum(nz, axis=1) / n_safe[:, None]                 # (s, D)
    delta = jnp.max(freq, axis=1)
    rho = jnp.minimum(jnp.sum(freq * freq, axis=1), 1.0)
    return {
        "n": n, "d": d,
        "mean_feature_variance": mean_feature_variance,
        "sparsity": sparsity,
        "density": 1.0 - sparsity,
        "omega": omega,
        "omega_frac": omega / d_safe,
        "delta": delta,
        "rho": rho,
    }


def masked_grad_characters(flats, shard_mask, param_mask) -> Dict:
    """Slots-batched gradient-level characters under validity masks.

    ``flats``: ``(n_slots, M, P)`` zero-padded flattened per-shard grads;
    ``shard_mask`` ``(n_slots, M)`` / ``param_mask`` ``(n_slots, P)`` mark
    real shards/parameters.  Same proxies as
    `ScalabilityAdvisor.grad_characters`, mask-weighted so padding is an
    exact no-op."""
    sm = shard_mask[:, :, None]                      # (s, M, 1)
    pm = param_mask[:, None, :]                      # (s, 1, P)
    cell = sm * pm
    m = jnp.sum(shard_mask, axis=1)                  # (s,)
    p = jnp.sum(param_mask, axis=1)
    m_safe = jnp.maximum(m, 1.0)
    p_safe = jnp.maximum(p, 1.0)

    mean = jnp.sum(flats * cell, axis=1) / m_safe[:, None]       # (s, P)
    var = jnp.sum(((flats - mean[:, None, :]) * sm) ** 2 * pm,
                  axis=1) / m_safe[:, None]
    gvar = jnp.sum(var * param_mask, axis=1) / p_safe
    gmean_sq = jnp.sum((mean ** 2) * param_mask, axis=1) / p_safe
    sparsity = jnp.sum((jnp.abs(flats) <= SPARSITY_TOL) * cell,
                       axis=(1, 2)) / (m_safe * p_safe)

    normed = flats * cell / (
        jnp.linalg.norm(flats * cell, axis=2, keepdims=True) + 1e-9)
    cos = jnp.einsum("smp,snp->smn", normed, normed)
    pair = sm * shard_mask[:, None, :]               # (s, M, M)
    off = (jnp.sum(cos * pair, axis=(1, 2)) - m) / (m * (m - 1.0) + 1e-9)
    return {
        "grad_variance": gvar,
        "grad_noise_scale": gvar / (gmean_sq + 1e-12),
        "grad_sparsity": sparsity,
        "shard_cosine_similarity": off,
    }


#: default |g| <= tol sparsity threshold shared by the scalar and masked
#: gradient paths (ScalabilityAdvisor(sparsity_tol=) overrides per
#: instance for the scalar path)
SPARSITY_TOL = 1e-8


class ScalabilityAdvisor:
    def __init__(self, *, parallel_cost=1e-3, sparsity_tol=SPARSITY_TOL):
        self.parallel_cost = parallel_cost
        self.tol = sparsity_tol

    # -- input validation (the service front door hits these) ---------------
    @staticmethod
    def validate_grads(per_shard_grads) -> Optional[str]:
        """None when the shard list supports character measurement, else a
        human-readable reason (empty list, a single shard — no cross-shard
        signal — or non-finite gradient values)."""
        if per_shard_grads is None or len(per_shard_grads) == 0:
            return "empty shard list — no gradients to measure"
        if len(per_shard_grads) == 1:
            return ("single gradient shard — cross-shard variance and "
                    "similarity need >= 2 shards")
        for i, g in enumerate(per_shard_grads):
            leaves = jax.tree.leaves(g)
            if not leaves or all(x.size == 0 for x in map(jnp.asarray,
                                                          leaves)):
                return f"shard {i} carries no gradient values"
            if not all(bool(jnp.isfinite(jnp.asarray(x)).all())
                       for x in leaves):
                return f"shard {i} contains non-finite gradient values"
        return None

    @staticmethod
    def validate_dataset(X) -> Optional[str]:
        """None when X supports character measurement, else the reason
        (empty, not a matrix, < 2 rows, or non-finite values)."""
        if X is None:
            return "no dataset provided"
        X = jnp.asarray(X)
        if X.ndim != 2:
            return f"dataset must be a (rows, features) matrix, got " \
                   f"shape {tuple(X.shape)}"
        if X.shape[0] < 2 or X.shape[1] < 1:
            return (f"dataset of shape {tuple(X.shape)} is too small — "
                    f"character measurement needs >= 2 rows and >= 1 "
                    f"feature")
        if not bool(jnp.isfinite(X).all()):
            return "dataset contains non-finite values"
        return None

    @staticmethod
    def invalid_report(kind: str, reason: str) -> Dict:
        """Structured low-confidence report for an unmeasurable probe: the
        conservative m_max is 1 worker, confidence is 0, and the caller is
        told to fix the probe — never NaN characters, never a raise."""
        return {
            "valid": False, "kind": kind, "reason": reason,
            "confidence": 0.0,
            "predicted_m_max_conservative": 1,
            "recommendation": (f"invalid {kind} probe: {reason}; fix the "
                               f"probe input — no scalability estimate is "
                               f"trustworthy for it"),
        }

    # -- gradient-level characters (production tier) ------------------------
    def grad_characters(self, per_shard_grads: List) -> Dict:
        """per_shard_grads: list of grad pytrees, one per data shard (or per
        microbatch) — the sample-difference proxies of §IV measured on the
        gradients the optimizer actually consumes."""
        flats = jnp.stack([_flatten(g) for g in per_shard_grads])   # (m, P)
        gvar = float(jnp.mean(jnp.var(flats, axis=0)))
        gmean_sq = float(jnp.mean(jnp.mean(flats, axis=0) ** 2))
        sparsity = float(jnp.mean(jnp.abs(flats) <= self.tol))
        # pairwise cosine similarity across shards = LS proxy
        normed = flats / (jnp.linalg.norm(flats, axis=1, keepdims=True) + 1e-9)
        cos = normed @ normed.T
        m = flats.shape[0]
        off = (jnp.sum(cos) - m) / (m * (m - 1) + 1e-9)
        return {
            "grad_variance": gvar,
            "grad_noise_scale": gvar / (gmean_sq + 1e-12),
            "grad_sparsity": sparsity,
            "shard_cosine_similarity": float(off),
        }

    def _grad_report(self, ch: Dict) -> Dict:
        """Predictions + recommendation from measured gradient characters
        (shared by the scalar `from_grads` and the service's batched path,
        so the two produce identical answers for identical characters)."""
        # gradient-noise-scale plays sigma's role in the Thm 3 curve;
        # the m-search is the vectorized grid scan, not a Python loop
        sigma = ch["grad_noise_scale"] ** 0.5
        ch["predicted_m_max_sync"] = FIT.sync_mmax(sigma, self.parallel_cost)
        # Hogwild staleness tolerance needs gradient sparsity
        om = (1.0 - ch["grad_sparsity"])
        ch["predicted_m_max_stale"] = max(
            1, int((1.0 / (6.0 * max(om, 1e-6))) ** 0.5))
        ch["recommendation"] = self._recommend(ch)
        ch["valid"] = True
        return ch

    def from_grads(self, per_shard_grads: List) -> Dict:
        reason = self.validate_grads(per_shard_grads)
        if reason is not None:
            return self.invalid_report("grads", reason)
        return self._grad_report(self.grad_characters(per_shard_grads))

    # -- dataset-level characters (faithful tier) ---------------------------
    def from_dataset(self, X, *, tau_max=8, batch_size=8, beta=0.9,
                     sync_every=4, anchor_every=100) -> Dict:
        reason = self.validate_dataset(X)
        if reason is not None:
            return self.invalid_report("dataset", reason)
        ch = MX.summarize(X, tau_max=tau_max, batch_size=batch_size)
        ch["hogwild"] = FIT.predict_hogwild_mmax(X)
        ch["sync"] = FIT.predict_sync_mmax(X, parallel_cost=self.parallel_cost)
        ch["dadm"] = FIT.predict_dadm_mmax(X, parallel_cost=self.parallel_cost)
        # critical-parameter envelopes: same characters, knob-shifted cliffs
        ch["momentum"] = FIT.predict_momentum_mmax(
            X, beta=beta, parallel_cost=self.parallel_cost)
        ch["local_sgd"] = FIT.predict_local_sgd_mmax(
            X, sync_every=sync_every, parallel_cost=self.parallel_cost)
        ch["svrg"] = FIT.predict_svrg_mmax(X, anchor_every=anchor_every)
        ch["recommendation"] = self._recommend_dataset(ch)
        ch["valid"] = True
        return ch

    # -- batched probes (one jitted masked-batch call for N requests) -------
    def dataset_characters_batch(self, Xs: List, n_slots: int = 0
                                 ) -> List[Optional[Dict]]:
        """Characters for N raw datasets in ONE masked-batch computation.

        Pads every dataset to the group's (rows, features) envelope and a
        slot count of ``max(n_slots, len(Xs))``, runs
        :func:`masked_dataset_characters` once, then finishes the one
        non-vmappable index (exact-dedup `diversity`) per slot: on the
        device for a device array, with `np.unique` for a NumPy one.
        Invalid entries come back as None (callers pair them with
        :meth:`invalid_report`); the returned dicts carry exactly the
        characters the `repro.analysis.fit` ``*_from_characters``
        predictors consume."""
        reasons = [self.validate_dataset(X) for X in Xs]
        valid = [i for i, r in enumerate(reasons) if r is None]
        out: List[Optional[Dict]] = [None] * len(Xs)
        if not valid:
            return out
        slots = max(int(n_slots), len(Xs))
        arrs = [jnp.asarray(Xs[i], jnp.float32) for i in valid]
        R = max(a.shape[0] for a in arrs)
        D = max(a.shape[1] for a in arrs)
        Xp = jnp.zeros((slots, R, D), jnp.float32)
        row_m = jnp.zeros((slots, R), jnp.float32)
        col_m = jnp.zeros((slots, D), jnp.float32)
        for s, a in enumerate(arrs):
            Xp = Xp.at[s, :a.shape[0], :a.shape[1]].set(a)
            row_m = row_m.at[s, :a.shape[0]].set(1.0)
            col_m = col_m.at[s, :a.shape[1]].set(1.0)
        batched = _masked_dataset_characters_jit(Xp, row_m, col_m)
        batched = jax.device_get(batched)
        for s, i in enumerate(valid):
            ch = {k: (int(v[s]) if k in ("n", "d") else float(v[s]))
                  for k, v in batched.items()}
            # exact row dedup of the unpadded rows: a masked envelope
            # would pad in rows of zeros that count as a kind
            ch["diversity"] = MX.diversity(Xs[i])
            ch["diversity_ratio"] = ch["diversity"] / max(ch["n"], 1)
            out[i] = ch
        return out

    def grad_characters_batch(self, grads_list: List, n_slots: int = 0
                              ) -> List[Optional[Dict]]:
        """Gradient characters for N per-shard-grad probes in ONE masked
        batch (the `from_grads` twin of :meth:`dataset_characters_batch`);
        invalid entries come back as None."""
        reasons = [self.validate_grads(g) for g in grads_list]
        valid = [i for i, r in enumerate(reasons) if r is None]
        out: List[Optional[Dict]] = [None] * len(grads_list)
        if not valid:
            return out
        slots = max(int(n_slots), len(grads_list))
        flats = [[_flatten(g) for g in grads_list[i]] for i in valid]
        M_ = max(len(f) for f in flats)
        P = max(f[0].shape[0] for f in flats)
        Fp = jnp.zeros((slots, M_, P), jnp.float32)
        shard_m = jnp.zeros((slots, M_), jnp.float32)
        param_m = jnp.zeros((slots, P), jnp.float32)
        for s, shards in enumerate(flats):
            for j, f in enumerate(shards):
                Fp = Fp.at[s, j, :f.shape[0]].set(f)
            shard_m = shard_m.at[s, :len(shards)].set(1.0)
            param_m = param_m.at[s, :shards[0].shape[0]].set(1.0)
        batched = jax.device_get(
            _masked_grad_characters_jit(Fp, shard_m, param_m))
        for s, i in enumerate(valid):
            out[i] = {k: float(v[s]) for k, v in batched.items()}
        return out

    def _recommend(self, ch: Dict) -> str:
        if ch["grad_sparsity"] > 0.5:
            return ("sparse gradients: async/stale exchange scales "
                    f"(predicted m_max ~{ch['predicted_m_max_stale']}); "
                    "sync batch scaling limited")
        if ch["grad_noise_scale"] > 1.0:
            return ("high gradient noise: sync batch scaling pays off up to "
                    f"m~{ch['predicted_m_max_sync']}")
        return ("low gradient noise: batch scaling saturates early "
                f"(m_max~{ch['predicted_m_max_sync']}); consider gossip to "
                "cut exchange cost instead of adding workers")

    def _recommend_dataset(self, ch: Dict) -> str:
        if ch["sparsity"] > 0.9:
            return ("sparse + low-variance dataset: Hogwild!-class (predicted "
                    f"m_max {ch['hogwild']['predicted_m_max']}, "
                    f"{ch['svrg']['predicted_m_max']} with semi-stochastic "
                    "gradients); mini-batch gains will be minor (paper "
                    "Fig 3b)")
        if ch["mean_feature_variance"] > 1.0:
            return ("dense high-variance dataset: mini-batch SGD/ECD-PSGD "
                    f"class, m_max ~{ch['sync']['predicted_m_max']} "
                    "(paper Fig 3a)")
        if ch["diversity_ratio"] < 0.5:
            return ("low diversity: DADM and all model-average methods "
                    "saturate early (paper Fig 6); deduplicate or reshuffle")
        return ("balanced characters: any strategy; bound set by parallel "
                "cost — a local-SGD sync window amortizes it (predicted "
                f"m_max {ch['local_sgd']['predicted_m_max']} vs sync "
                f"{ch['sync']['predicted_m_max']})")


_masked_dataset_characters_jit = jax.jit(masked_dataset_characters)
_masked_grad_characters_jit = jax.jit(masked_grad_characters)
