"""Operation and byte counts against hand counts at small shapes, and the
peaks table."""

import pytest

from harness import cells, work

# m=2, d=3, n_train=7, n_test=2, iters=4, eval_every=2 (2 evals)
ARGS = (2, 3, 7, 2, 4, 2)
EVALS = 2 * (2 * 2 * 3 + 6 * 2)                 # 2 x 24
POINT_GRAD = 2 * 3 + 1 + 4 + 2 + 3 * 3          # 22


def test_hogwild_count():
    step = POINT_GRAD + 2 * 3                   # 28
    assert cells.algorithm_flops("hogwild").flops(*ARGS, {}) == (
        4 * step + EVALS)


def test_minibatch_count():
    step = 12 + 16 + 12 + 3 + 6 + 6             # 55
    assert cells.algorithm_flops("minibatch").flops(*ARGS, {}) == (
        4 * step + EVALS)


def test_ecd_psgd_count():
    quantize = 6 + 2 + 3 + 3 + 3 + 6 + 3        # 26
    step = 18 + 2 * POINT_GRAD + 12 + 20 + 2 * quantize + 21   # 167
    readout = 2 * 2 * 3
    assert cells.algorithm_flops("ecd_psgd").flops(*ARGS, {}) == (
        4 * step + readout + EVALS)


def test_dadm_count():
    b = 2
    init = 2 * 7 * 3 + 28 + 7 + 2 * 7 * 3 + 3   # 122
    worker = 12 + 16 + 2 + 12 + 3               # 45
    step = 2 * worker + 2 * b + 2 * 3           # 100
    assert cells.algorithm_flops("dadm").flops(
        *ARGS, {"local_batch": b}) == init + 4 * step + EVALS


def test_characters_program_counts():
    k = cells.kernel_counts("csim")
    assert k.ops(5, 3, 2) == 4 * 5 * 3 * 2
    assert k.bytes_moved(5, 3, 2) == 60 + 4
    k = cells.kernel_counts("pairwise_l0")
    assert k.ops(2, 4, 3) == 4 * 2 * 4 * 3 * 3
    assert k.bytes_moved(2, 4, 3) == 96 + 8


def test_sweep_flops_sums_jobs_m_and_seeds():
    spec = {"ms": [1, 2], "iters": 4, "eval_every": 2, "n_seeds": 3,
            "datasets": {"a": {"kwargs": {"n": 10, "d": 3}}},
            "jobs": [{"algorithm": "hogwild", "dataset": "a"}]}
    one = cells.algorithm_flops("hogwild").flops
    assert work.sweep_flops(spec) == 3 * (one(1, 3, 7, 2, 4, 2, {})
                                          + one(2, 3, 7, 2, 4, 2, {}))


def test_an_algorithm_without_a_count_is_an_error():
    spec = {"ms": [1], "iters": 4, "eval_every": 2,
            "datasets": {"a": {"kwargs": {"n": 10, "d": 3}}},
            "jobs": [{"algorithm": "momentum", "dataset": "a"}]}
    with pytest.raises(KeyError):
        work.sweep_flops(spec)


def test_calls_of_the_characters_programs():
    spec = {"characters_rows": 0, "measure_csim": 8, "csim_rows": 400,
            "datasets": {"a": {"kwargs": {"n": 2400, "d": 28}},
                         "b": {"kwargs": {"n": 300, "d": 5}}}}
    assert work.character_calls(spec) == {
        "csim": [(512, 28, 8), (400, 28, 8), (300, 5, 8), (300, 5, 8)],
        "pairwise_l0": [(64, 8, 28), (37, 8, 5)]}


def test_peaks_are_keyed_by_device_kind():
    assert cells.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        cells.peaks("cpu")
