"""Pieces every algorithm's count shares (see README.md)."""

SIGMOID = 4                      # exp, add, reciprocal, negate


def test_loss(n_test: int, d: int) -> int:
    """mean(logaddexp(0, -y * (X @ x))) over the held-out rows."""
    return 2 * n_test * d + 6 * n_test


def point_grad(d: int) -> int:
    """-sigmoid(-y xi.x) y xi + lam x at one sample."""
    return 2 * d + 1 + SIGMOID + 2 + 3 * d


def evals(iters: int, eval_every: int, n_test: int, d: int) -> int:
    return (iters // eval_every) * test_loss(n_test, d)
