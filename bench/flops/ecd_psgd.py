"""ECD-PSGD (paper Alg 4): m workers on a ring, each with its own model,
exchanging stochastically quantized extrapolations."""

import _common as C


def flops(m, d, n_train, n_test, iters, eval_every, kwargs):
    quantize = 2 * d + 2 + d + d + d + 2 * d + d   # max|z|, scale, /, +u,
    #                                                floor, clip, dequantize
    step = (3 * m * d                  # ring average of three neighbours
            + m * C.point_grad(d)
            + 2 * m * d                # x_half - gamma * g
            + 3 * m * d + 2            # z = (1 - t/2) x + (t/2) x_new
            + m * quantize
            + 3 * m * d + 3)           # y = (1 - 2/t) y + (2/t) C(z)
    readout = (iters // eval_every) * m * d        # mean over workers
    return iters * step + readout + C.evals(iters, eval_every, n_test, d)
