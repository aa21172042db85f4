"""Paper-core tests: metrics definitions, algorithm convergence, and the
paper's qualitative claims (the EXPERIMENTS.md validation in miniature)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import metrics as MX
from repro.core import scalability as SC
from repro.core.advisor import ScalabilityAdvisor
from repro.core.algorithms import (run_dadm, run_ecd_psgd, run_hogwild,
                                   run_minibatch)
from repro.data import synth

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# metrics (§IV)
# ---------------------------------------------------------------------------

def test_example2_csim_orderings():
    """Paper Example 2: same 6 samples, two orderings, different C_sim_2."""
    seq1 = jnp.array([[0, 0, 0], [0, 0, 1], [0, 1, 1],
                      [0, 1, 0], [1, 1, 0], [1, 0, 0]], jnp.float32)
    perm = jnp.array([0, 4, 1, 5, 3, 2])
    seq2 = seq1[perm]
    c1 = MX.csim_ref(seq1, 2)
    c2 = MX.csim_ref(seq2, 2)
    assert c1 != c2
    assert c1 < c2           # adjacent-similar ordering has smaller C_sim


def test_sparsity_and_variance_relation():
    """Paper §IV.B: sparse dataset => small feature variance."""
    sparse = synth.make_realsim_like(KEY, n=500, d=200, density=0.03)
    dense = synth.make_higgs_like(KEY, n=500, d=28)
    assert MX.sparsity(sparse.X) > 0.9
    assert MX.sparsity(dense.X) < 0.05
    assert (MX.mean_feature_variance(sparse.X)
            < MX.mean_feature_variance(dense.X))


def test_diversity_constructions():
    """real_sim2 / real_sim4 duplication halves/quarters diversity."""
    base = synth.make_realsim_like(KEY, n=400, d=100)
    high, mid, low = synth.make_diversity_variants(base)
    dh, dm, dl = (MX.diversity(x.X) for x in (high, mid, low))
    # sparse random rows can collide, so compare ratios, not exact counts
    assert dh > 0.9 * 400
    assert dm < 0.6 * dh and dl < 0.35 * dh
    assert high.X.shape == mid.X.shape == low.X.shape


def test_one_sample_dataset_diversity():
    """Paper Example 12: size can grow, diversity stays 1."""
    ds = synth.make_one_sample_dataset(KEY, n=256, d=16)
    assert MX.diversity(ds.X) == 1
    assert MX.diversity_ratio(ds.X) == pytest.approx(1 / 256)


def test_ls_sequences_order():
    small = synth.make_ls_sequence(KEY, n=400, d=50, mutate_frac=0.1)
    large = synth.make_ls_sequence(KEY, n=400, d=50, mutate_frac=0.9)
    assert MX.csim_ref(small.X, 4) < MX.csim_ref(large.X, 4)


@settings(max_examples=15, deadline=None)
@given(st.integers(10, 60), st.integers(5, 40))
def test_sparsity_bounds(n, d):
    X = jax.random.normal(jax.random.PRNGKey(n * d), (n, d))
    assert 0.0 <= MX.sparsity(X) <= 1.0
    assert MX.diversity(X) <= n
    hw = MX.hogwild_params(X)
    assert 0.0 <= hw["delta"] <= 1.0 and 0.0 <= hw["rho"] <= 1.0
    assert 0 <= hw["omega_frac"] <= 1.0


# ---------------------------------------------------------------------------
# algorithms converge on their suitable datasets
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dense_split():
    ds = synth.make_higgs_like(KEY, n=2000, d=28)
    return ds.split(key=KEY)


@pytest.fixture(scope="module")
def sparse_split():
    ds = synth.make_realsim_like(KEY, n=2000, d=400, density=0.05)
    return ds.split(key=KEY)


@pytest.mark.parametrize("runner,kw", [
    (run_hogwild, {"m": 4}),
    (run_minibatch, {"batch_size": 4}),
    pytest.param(run_ecd_psgd, {"m": 4}, marks=pytest.mark.xfail(
        strict=True,
        reason="root-caused (ISSUE 6): ECD-PSGD's z-extrapolation range "
               "grows ~t*gamma, so stochastic-quantization noise "
               "(~ range * 2^-bits, injected with weight 2/t) settles at a "
               "constant floor ~ gamma * 2^-bits that exceeds this split's "
               "optimality gap at gamma=0.1 / 8 bits — faithful algorithm "
               "behaviour at an aggressive operating point, not an engine "
               "bug; see test_ecd_psgd_quantization_noise_floor")),
    (run_dadm, {"m": 4}),
])
def test_algorithms_decrease_loss(dense_split, runner, kw):
    tr, te = dense_split
    r = runner(tr, te, iters=1500, eval_every=100, **kw)
    assert r["losses"][-1] < r["losses"][0]
    assert np.isfinite(r["losses"]).all()


def test_ecd_psgd_quantization_noise_floor(dense_split):
    """Regression pin for the strict xfail above: the non-descent is the
    step-size x quantization interaction, so shrinking the noise floor on
    EITHER axis — more bits at the same gamma, or a smaller gamma at the
    same bits — restores descent on the identical split/seed/budget."""
    tr, te = dense_split
    kw = dict(m=4, iters=1500, eval_every=100)
    at_fault = run_ecd_psgd(tr, te, gamma=0.1, compress_bits=8, **kw)
    finer = run_ecd_psgd(tr, te, gamma=0.1, compress_bits=16, **kw)
    smaller = run_ecd_psgd(tr, te, gamma=0.02, compress_bits=8, **kw)
    # the failing point descends mid-run then wanders at its noise floor
    assert min(at_fault["losses"]) < at_fault["losses"][0]
    assert not at_fault["losses"][-1] < at_fault["losses"][0]
    for fixed in (finer, smaller):
        assert fixed["losses"][-1] < fixed["losses"][0]
        assert fixed["losses"][-1] < at_fault["losses"][-1]


def test_ecd_psgd_divergence_envelope():
    """Enforce the documented ECD-PSGD exemption (docs/distributed.md):
    stochastic quantization makes every execution-mode comparison chaotic
    at long horizons — but inside a measured envelope.  At 60 iterations
    the modes agree essentially exactly; by 120+ the same ulp-level
    reconvergence noise is amplified to the ~1e-2 class, and no further.
    A blow-up past the envelope (or a silent return to exactness after an
    engine change that skirts the quantizer) fails this pin."""
    from repro.experiments import engine

    key = jax.random.PRNGKey(0)
    ds = synth.make_higgs_like(key, n=160, d=10)
    tr, te = ds.split(key=key)
    ms = [1, 2, 4, 8]

    def modes(iters):
        kw = dict(iters=iters, eval_every=20, key=key)
        b = engine.sweep("ecd_psgd", tr, te, ms, bucketed=True, **kw)
        f = engine.sweep("ecd_psgd", tr, te, ms, bucketed=False, **kw)
        s = engine.sweep("ecd_psgd", tr, te, ms, use_vmap=False, **kw)
        return (np.asarray(b["losses"]), np.asarray(f["losses"]),
                np.asarray(s["losses"]))

    b60, f60, s60 = modes(60)
    # short horizon: bucketed==flat to float32 ulps, sequential near-exact
    np.testing.assert_allclose(b60, f60, rtol=0, atol=1e-6)
    np.testing.assert_allclose(f60, s60, rtol=0, atol=1e-3)

    b120, f120, s120 = modes(120)
    for a, b in ((b120, f120), (f120, s120)):
        assert np.isfinite(a).all() and np.isfinite(b).all()
        assert np.abs(a - b).max() <= 2e-2    # the documented ~1e-2 class


@pytest.mark.slow
def test_paper_fig3_variance_sparsity_trend(dense_split, sparse_split):
    """Fig 3: mini-batch parallel gain is large on the dense/high-variance
    dataset and minor on the sparse dataset (gap between m=1 and m=8)."""
    gaps = {}
    for name, (tr, te) in [("dense", dense_split), ("sparse", sparse_split)]:
        r1 = run_minibatch(tr, te, batch_size=1, iters=800, eval_every=100)
        r8 = run_minibatch(tr, te, batch_size=8, iters=800, eval_every=100)
        gaps[name] = float(np.mean(np.array(r1["losses"])
                                   - np.array(r8["losses"])))
    assert gaps["dense"] > gaps["sparse"]
    assert gaps["dense"] > 0


@pytest.mark.slow
def test_paper_fig5_hogwild_sparse_tolerance(dense_split, sparse_split):
    """Fig 5: Hogwild!'s staleness penalty (gap between m=1 and m=8 at fixed
    server iteration) is smaller on the sparse dataset."""
    gap = {}
    for name, (tr, te) in [("dense", dense_split), ("sparse", sparse_split)]:
        r1 = run_hogwild(tr, te, m=1, iters=1200, eval_every=100, gamma=0.05)
        r8 = run_hogwild(tr, te, m=8, iters=1200, eval_every=100, gamma=0.05)
        gap[name] = float(np.mean(np.abs(np.array(r8["losses"])
                                         - np.array(r1["losses"]))))
    assert gap["sparse"] < gap["dense"]


@pytest.mark.slow
def test_paper_fig6_dadm_diversity(sparse_split):
    """Fig 6: DADM's parallel gain shrinks as diversity drops."""
    base = synth.make_realsim_like(KEY, n=1600, d=300, density=0.05)
    high, mid, low = synth.make_diversity_variants(base)
    gains = []
    for ds in (high, low):
        tr, te = ds.split(key=KEY)
        r1 = run_dadm(tr, te, m=1, iters=400, eval_every=100)
        r8 = run_dadm(tr, te, m=8, iters=400, eval_every=100)
        gains.append(float(np.mean(np.array(r1["losses"])
                                   - np.array(r8["losses"]))))
    assert gains[0] > gains[1]    # high diversity gains more from m=8


# ---------------------------------------------------------------------------
# scalability machinery
# ---------------------------------------------------------------------------

def test_gain_growth_and_upper_bound():
    costs = [100.0, 60.0, 45.0, 40.0, 41.0, 44.0]
    gg = SC.gain_growth_from_costs(costs)
    assert gg[0] == 40.0
    ms = [1, 2, 4, 8, 16, 24]
    assert SC.measured_upper_bound(ms[:-1], gg) == 8   # growth <= 0 at m=8


def test_hogwild_mmax_ordering():
    sparse = synth.make_realsim_like(KEY, n=600, d=400, density=0.03)
    dense = synth.make_higgs_like(KEY, n=600, d=28)
    ms = SC.predict_hogwild_mmax(sparse.X)["predicted_m_max"]
    md = SC.predict_hogwild_mmax(dense.X)["predicted_m_max"]
    assert ms > md        # paper Fig 1/2: sparse suits Hogwild!


def test_advisor_reports():
    adv = ScalabilityAdvisor()
    sparse = synth.make_realsim_like(KEY, n=300, d=200)
    rep = adv.from_dataset(sparse.X, tau_max=4, batch_size=4)
    assert "recommendation" in rep and rep["hogwild"]["predicted_m_max"] >= 1
    # gradient-level: fabricate shard grads with known sparsity
    g1 = {"w": jnp.array([0.0, 1.0, 0.0, 0.0])}
    g2 = {"w": jnp.array([0.0, 0.9, 0.0, 0.0])}
    rep = adv.from_grads([g1, g2])
    assert rep["grad_sparsity"] == pytest.approx(0.75)
    assert rep["shard_cosine_similarity"] == pytest.approx(1.0, abs=1e-5)


def test_iterations_to_epsilon():
    losses = np.array([0.9, 0.7, 0.5, 0.3])
    assert SC.iterations_to_epsilon(losses, 100, 0.5) == 300
    assert SC.iterations_to_epsilon(losses, 100, 0.1) == np.inf


def test_advisor_invalid_probes_are_structured():
    """Edge-case probes return a structured low-confidence report (valid
    False + reason + conservative m_max 1) — never NaN, never a raise."""
    adv = ScalabilityAdvisor()
    cases = [
        (adv.from_grads([]), "empty shard list"),
        (adv.from_grads(None), "empty shard list"),
        (adv.from_grads([{"w": jnp.ones(3)}]), "single gradient shard"),
        (adv.from_grads([{"w": jnp.ones(3)},
                         {"w": jnp.array([1.0, np.nan, 0.0])}]),
         "non-finite gradient"),
        (adv.from_dataset(None), "no dataset"),
        (adv.from_dataset(jnp.ones(5)), "matrix"),
        (adv.from_dataset(jnp.ones((1, 4))), "too small"),
        (adv.from_dataset(jnp.full((6, 3), np.inf)), "non-finite"),
    ]
    for rep, frag in cases:
        assert rep["valid"] is False, frag
        assert frag in rep["reason"], rep["reason"]
        assert rep["confidence"] == 0.0
        assert rep["predicted_m_max_conservative"] == 1
        assert "recommendation" in rep
        assert all(np.isfinite(v) for v in rep.values()
                   if isinstance(v, float))


def test_advisor_valid_reports_flagged_valid():
    adv = ScalabilityAdvisor()
    data = synth.make_higgs_like(KEY, n=80, d=6)
    assert adv.from_dataset(data.X)["valid"] is True
    grads = [{"w": jnp.ones(4) * i} for i in (1, 2)]
    assert adv.from_grads(grads)["valid"] is True


def test_advisor_batched_characters_match_scalar():
    """The masked-batch probe paths agree with the scalar paths and mark
    invalid entries None."""
    adv = ScalabilityAdvisor()
    X_ok = np.asarray(synth.make_realsim_like(KEY, n=60, d=40).X)
    X_bad = np.full((4, 2), np.nan)
    out = adv.dataset_characters_batch([X_ok, X_bad, X_ok[:30, :10]])
    assert out[1] is None
    seq = adv.from_dataset(X_ok)
    for k in ("mean_feature_variance", "sparsity", "omega_frac",
              "delta", "rho"):
        assert out[0][k] == pytest.approx(seq[k], abs=1e-6), k
    assert out[0]["diversity"] == seq["diversity"]

    g_ok = [{"w": jnp.arange(4.0)}, {"w": jnp.arange(4.0) * 2}]
    gout = adv.grad_characters_batch([g_ok, [], g_ok])
    assert gout[1] is None
    gseq = adv.grad_characters(g_ok)
    for k in gseq:
        assert gout[0][k] == pytest.approx(gseq[k], abs=1e-5), k
