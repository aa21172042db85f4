"""The per-layer metrics read from the program's spans, on hand-made
spans (Chrome-trace events: ``ts`` and ``dur`` in microseconds)."""

import pytest

from harness import cells


def span(name, ts, dur, depth=0, /, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur,
            "args": dict(args, depth=depth)}


def read(metric, spans, sweeps=1):
    return cells.metric_reader(metric).read({"spans": spans,
                                             "sweeps": sweeps})


SWEEP = [
    span("sweep", 0, 10e6),
    span("datasets", 0, 3e6, 1),
    span("dataset_build", 0, 1.0e6, 2, name="a", n=2400, d=28),
    span("characters", 1.0e6, 0.5e6, 2, name="a"),
    span("dataset_build", 1.5e6, 1.0e6, 2, name="b", n=2400, d=200),
    span("characters", 2.5e6, 0.25e6, 2, name="b"),
    span("bucket", 4e6, 3e6, 3, algorithm="minibatch", m_pad=8),
    # a trace with one nested inside it, then the lowering, then XLA
    span("jaxpr_trace", 4e6, 1e6, 4, fun_name="bucket_minibatch_m8"),
    span("jaxpr_trace", 4.2e6, 0.3e6, 5, fun_name="norm"),
    span("lower", 5e6, 0.75e6, 4, fun_name="jit(bucket_minibatch_m8)"),
    span("compile", 5.75e6, 1e6, 4, fun_name="jit(bucket_minibatch_m8)"),
    span("fetch", 7e6, 0.5e6, 3),
]


def test_lower_counts_nested_traces_once():
    assert read("lower_s_per_sweep", SWEEP) == pytest.approx(1.75)
    assert read("lower_s_per_sweep", SWEEP, sweeps=2) == pytest.approx(0.875)


def test_dataset_build_and_characters_sum_their_spans():
    assert read("dataset_build_s_per_sweep", SWEEP) == pytest.approx(2.0)
    assert read("characters_s_per_sweep", SWEEP) == pytest.approx(0.75)


@pytest.mark.parametrize("metric", ["lower_s_per_sweep",
                                    "dataset_build_s_per_sweep",
                                    "characters_s_per_sweep"])
def test_nothing_to_read_gives_none(metric):
    # a program without these spans (an older commit) reports nothing
    assert read(metric, [span("sweep", 0, 1e6), span("datasets", 0, 5e5, 1)]) \
        is None
