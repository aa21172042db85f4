"""The device count of distinct rows (`core.metrics._row_kinds`): the
same number as the host's `np.unique` over the rounded rows, on every
generator of the repo and on the cases where rounding decides; a count
that cannot vouch for itself falls back to the host."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import metrics as MX
from repro.data import synth
from repro.telemetry import instrument, metrics  # noqa: F401 (compile listener)

KEY = jax.random.PRNGKey(3)


def host_kinds(X):
    return int(np.unique(np.round(np.asarray(X), 6), axis=0).shape[0])


def counts():
    return {p: metrics.REGISTRY.counter(
        "repro_diversity_counts_total", labels={"path": p}).value
        for p in ("device", "host", "fallback")}


def delta(before):
    return {p: v - before[p] for p, v in counts().items() if v != before[p]}


def with_repeats(X):
    """X with every third row repeated further down, so kinds < rows."""
    return jnp.concatenate([X, X[::3]])


def _variant(i):
    sim = synth.make_realsim_like(KEY, n=96, d=300, density=0.05)
    return synth.make_diversity_variants(sim)[i].X


def _last_column():
    X = np.tile(np.linspace(-1.0, 1.0, 300, dtype=np.float32), (4, 1))
    X[1::2, -1] += 0.5                     # rows 1 and 3 alike
    return jnp.asarray(X)


#: name -> the rows, built when the case runs
CASES = {
    "realsim_d20958": lambda: synth.make_realsim_like(
        KEY, n=48, d=20958, density=0.0025).X,
    "realsim_d300": lambda: synth.make_realsim_like(
        KEY, n=96, d=300, density=0.05).X,
    "higgs_like": lambda: synth.make_higgs_like(KEY, n=120, d=28).X,
    "upper_bound": lambda: synth.make_upper_bound_dataset(
        KEY, n=120, d=400).X,
    "ls_sequence": lambda: synth.make_ls_sequence(
        KEY, n=120, d=28, mutate_frac=0.05).X,
    "variants_high": lambda: _variant(0),
    "variants_mid": lambda: _variant(1),
    "variants_low": lambda: _variant(2),
    "one_sample": lambda: synth.make_one_sample_dataset(KEY, n=64, d=64).X,
    # -0.0 and 0.0 are one value, as np.unique compares them
    "signed_zero": lambda: jnp.asarray(
        [[0.0, 1.0], [-0.0, 1.0], [0.5, -0.0], [0.5, 0.0],
         [-1e-7, 2.0], [1e-7, 2.0]], jnp.float32),
    # differences below the sixth decimal merge; one at it does not
    "below_rounding": lambda: jnp.asarray(
        [[0.1234561, 3.0], [0.1234564, 3.0], [0.1234571, 3.0],
         [-2.5000001, 1.0], [-2.5, 1.0]], jnp.float32),
    "last_column": _last_column,
}


@pytest.mark.parametrize("name", list(CASES))
def test_device_count_is_np_unique(name):
    X = CASES[name]()
    for x in (X, with_repeats(X)):
        c0 = counts()
        kinds = MX.diversity(x)
        assert delta(c0) == {"device": 1}
        assert kinds == host_kinds(x)


@pytest.fixture
def fresh_row_kinds():
    """`_row_kinds` traced afresh before and after the test, so patched
    weights neither meet an older program nor outlive the test."""
    MX._row_kinds.clear_cache()
    yield
    MX._row_kinds.clear_cache()


def test_forced_collision_falls_back_exactly(monkeypatch, fresh_row_kinds):
    """With every weight zero every row hashes alike: the neighbour check
    sees different rows under one hash, and the host answers."""
    monkeypatch.setattr(MX, "_hash_weights",
                        lambda d: jnp.zeros((2, d), jnp.uint32))
    X = with_repeats(synth.make_higgs_like(KEY, n=40, d=28).X)
    fetched = metrics.REGISTRY.counter("repro_host_fetch_bytes_total",
                                       labels={"site": "diversity"})
    c0, f0 = counts(), fetched.value
    assert MX.diversity(X) == host_kinds(X) == 40
    assert delta(c0) == {"fallback": 1}
    assert fetched.value - f0 == X.size * 4


@pytest.mark.parametrize("value", [np.nan, 1e9], ids=["nan", "large"])
def test_nan_or_large_value_falls_back(value):
    """A NaN never compares equal, and past 16 the host's division by 1e6
    can merge neighbouring values: either way the host answers."""
    X = np.tile(np.linspace(0, 1, 8, dtype=np.float32), (6, 1))
    X[::2, 0] += np.arange(3, dtype=np.float32)
    X[4, 3] = value
    c0 = counts()
    assert MX.diversity(jnp.asarray(X)) == host_kinds(X)
    assert delta(c0) == {"fallback": 1}


def test_second_call_at_a_shape_compiles_nothing():
    compiles = metrics.REGISTRY.counter("repro_jax_compiles_total")
    a = synth.make_realsim_like(KEY, n=72, d=500, density=0.02).X
    b = synth.make_realsim_like(jax.random.PRNGKey(9), n=72, d=500,
                                density=0.02).X
    MX.diversity(a)
    c0 = compiles.value
    assert MX.diversity(b) == host_kinds(b)
    assert compiles.value == c0
