"""Probe batching front end: N concurrent dataset-character probes ->
ONE jitted masked-batch call.

Built on `serve.SlotDriver` — the continuous-batching-lite driver of the
serving tier.  The slot state is a fixed ``(n_slots, max_rows,
max_cols)`` envelope plus row/column validity masks; each admitted probe
pads its dataset into a free slot, and one driver step runs
`core.advisor.masked_dataset_characters` over the whole slot batch (one
jitted dispatch regardless of occupancy — padded slots are exact no-ops
because every reduction is mask-weighted).  Character probes finish in a
single step, so the driver's role here is the admission/masking
contract, shared verbatim with the LM serving loop.

Probes larger than the envelope can't ride the fixed-shape slot state;
they fall back to `ScalabilityAdvisor.dataset_characters_batch` (the
group-envelope masked batch — same kernel, per-group shapes) and are
counted in ``stats()["fallback"]``.

The one §IV character that can't be masked-batched is ``diversity``
(exact row dedup); it is finished per probe by `core.metrics.diversity`,
exactly as the scalar path does.  A probe's rows are host NumPy here, so
the count runs `np.unique` on the host, with nothing to pull.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import advisor as advisor_mod
from repro.core import metrics as MX
from repro.serve.engine import SlotDriver

#: the (n_slots,)-shaped characters the masked kernel produces; the
#: batcher turns each slot's slice into the scalar dict the
#: `analysis.fit` ``*_from_characters`` predictors consume
CHARACTER_KEYS = ("n", "d", "mean_feature_variance", "sparsity",
                  "density", "omega", "omega_frac", "delta", "rho")


class ProbeBatcher:
    """Coalesce dataset-character probes into slot-batched jitted calls."""

    def __init__(self, n_slots: int = 8, max_rows: int = 512,
                 max_cols: int = 64):
        self.n_slots = int(n_slots)
        self.max_rows = int(max_rows)
        self.max_cols = int(max_cols)
        self._advisor = advisor_mod.ScalabilityAdvisor()
        self.n_batched = 0
        self.n_fallback = 0
        self.n_steps = 0

        init_state = {
            "X": jnp.zeros((n_slots, max_rows, max_cols), jnp.float32),
            "row_mask": jnp.zeros((n_slots, max_rows), jnp.float32),
            "col_mask": jnp.zeros((n_slots, max_cols), jnp.float32),
            "characters": {k: jnp.zeros((n_slots,), jnp.float32)
                           for k in CHARACTER_KEYS},
        }

        def step_fn(state, active):
            ch = advisor_mod.masked_dataset_characters(
                state["X"], state["row_mask"], state["col_mask"])
            new_state = dict(state, characters=ch)
            # character probes are single-step: every active slot is done
            return new_state, jnp.ones((self.n_slots,), bool)

        self.driver = SlotDriver(step_fn, init_state, n_slots)

    # -- helpers ------------------------------------------------------------
    def _payload(self, X: np.ndarray) -> Dict:
        r, c = X.shape
        Xp = np.zeros((self.max_rows, self.max_cols), np.float32)
        Xp[:r, :c] = np.asarray(X, np.float32)
        rm = np.zeros(self.max_rows, np.float32)
        rm[:r] = 1.0
        cm = np.zeros(self.max_cols, np.float32)
        cm[:c] = 1.0
        return {"X": jnp.asarray(Xp), "row_mask": jnp.asarray(rm),
                "col_mask": jnp.asarray(cm)}

    @staticmethod
    def _finish(ch: Dict, X) -> Dict:
        """Scalar-ize a slot's character slice and add the exact-dedup
        diversity indices of the probe's (host) rows."""
        out = {k: (int(ch[k]) if k in ("n", "d") else float(ch[k]))
               for k in CHARACTER_KEYS}
        out["diversity"] = MX.diversity(X)
        out["diversity_ratio"] = out["diversity"] / max(out["n"], 1)
        return out

    # -- the batched measurement --------------------------------------------
    def measure(self, items: List[Tuple[object, np.ndarray]]
                ) -> Dict[object, Optional[Dict]]:
        """Characters for every (request_id, X) item, batched through the
        slot driver; invalid datasets map to None (the caller pairs them
        with `ScalabilityAdvisor.invalid_report`).  Items beyond
        ``n_slots`` recycle freed slots across extra steps — admission
        never blocks, it waits for the next step's free slots."""
        results: Dict[object, Optional[Dict]] = {}
        fallback: List[Tuple[object, np.ndarray]] = []
        pending: List[Tuple[object, np.ndarray]] = []
        for rid, X in items:
            reason = self._advisor.validate_dataset(X)
            if reason is not None:
                results[rid] = None
            elif (X.shape[0] > self.max_rows or X.shape[1] > self.max_cols):
                fallback.append((rid, X))
            else:
                pending.append((rid, np.asarray(X)))

        by_id = {rid: X for rid, X in pending}
        pending = list(pending)
        while pending or self.driver.n_active:
            while pending:
                rid, X = pending[0]
                if self.driver.admit(rid, self._payload(X)) is None:
                    break                     # slots full; step frees them
                pending.pop(0)
                self.n_batched += 1
            for rid, out in self.driver.step():
                ch = {k: out["characters"][k] for k in CHARACTER_KEYS}
                results[rid] = self._finish(ch, by_id[rid])
            self.n_steps += 1

        if fallback:
            # oversized probes: group-envelope masked batch (same kernel)
            self.n_fallback += len(fallback)
            chs = self._advisor.dataset_characters_batch(
                [X for _, X in fallback])
            for (rid, _), ch in zip(fallback, chs):
                results[rid] = ch
        return results

    def stats(self) -> Dict:
        return {"n_slots": self.n_slots,
                "envelope": [self.max_rows, self.max_cols],
                "batched": self.n_batched, "fallback": self.n_fallback,
                "steps": self.n_steps}
