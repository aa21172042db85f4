"""Mini-batch SGD (paper Alg 2): m one-sample gradients averaged per
server iteration."""

import _common as C


def flops(m, d, n_train, n_test, iters, eval_every, kwargs):
    step = (2 * m * d                  # Xb @ x
            + (4 + C.SIGMOID) * m      # -sigmoid(-y z) y
            + 2 * m * d                # c @ Xb
            + d + 2 * d                # / m, + lam x
            + 2 * d)                   # x - gamma * g
    return iters * step + C.evals(iters, eval_every, n_test, d)
