"""Hogwild! (paper Alg 1): one stale point gradient and one update per
server iteration, whatever the worker count."""

import _common as C


def flops(m, d, n_train, n_test, iters, eval_every, kwargs):
    step = C.point_grad(d) + 2 * d          # x - gamma * g
    return iters * step + C.evals(iters, eval_every, n_test, d)
