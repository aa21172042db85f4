"""DADM (paper Alg 3): m workers each take one SDCA step on a local batch
of b dual coordinates; the server sums their primal increments."""

import _common as C


def flops(m, d, n_train, n_test, iters, eval_every, kwargs):
    b = kwargs.get("local_batch", 8)
    n = n_train
    init = (2 * n * d + 4 * n          # squared norms, SDCA step factors
            + n + 2 * n * d + d)       # v0 = (y alpha0) @ X / (lam n)
    worker = (2 * b * d                # Xi @ v
              + (4 + C.SIGMOID) * b    # (sigmoid(-y z) - alpha) * step
              + b + 2 * b * d + d)     # (y da) @ Xi / (lam n)
    step = m * worker + m * b + m * d  # scatter-add alpha, sum increments
    return init + iters * step + C.evals(iters, eval_every, n_test, d)
