"""The plain reference that decides ``correct``.

It knows nothing of the program: it takes the configuration's matrix, the
value vectors and right-hand sides the benchmark drew from the seed, and the
solutions the timed window returned, and checks those solutions on the host
in float64 with scipy alone.

Two numbers are compared, each against a limit of its own:

* ``berr``: the worst componentwise backward error
  ``max_i |b - A x|_i / (|A| |x| + |b|)_i`` over every answer the window
  returned.  Its limit is the accuracy the configuration states.
* ``fwd_err``: the worst ``max|x - x_ref| / max|x_ref|`` over a sample of
  the answers drawn from the seed, where ``x_ref`` is scipy's ``splu``
  (SuperLU with partial pivoting) solution of the same system.  Its limit
  was set from readings of sound runs and of the lower-precision control.

An answer that is missing or not finite fails both.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.linalg import splu


def matrix(n, pattern, values):
    indptr, indices = pattern
    return sp.csc_matrix((values, indices, indptr), shape=(n, n))


def backward_error(S, absS, x, b) -> float:
    r = b - S @ x
    denom = absS @ np.abs(x) + np.abs(b)
    with np.errstate(divide="ignore", invalid="ignore"):
        e = np.abs(r) / denom
    e = np.where(denom == 0, np.where(r == 0, 0.0, np.inf), e)
    return float(np.max(e))


def forward_error(x, x_ref) -> float:
    return float(np.max(np.abs(x - x_ref)) / np.max(np.abs(x_ref)))


def check(n, pattern, answers, limits: dict, sample: int, seed: int):
    """Check every answer ``(key, values, rhs, x)`` of the window; answers
    with the same ``key`` share their values, so their matrix is built once.

    Returns ``(checks, failed)``: ``checks`` maps each compared number's
    name to ``{"value", "limit"}``, ``failed`` counts the answers that broke
    a limit.  ``sample`` answers, drawn from ``seed``, also get the
    forward-error comparison with ``splu``.
    """
    rng = np.random.default_rng([seed, 1])
    picked = set(rng.choice(len(answers), size=min(sample, len(answers)),
                            replace=False).tolist()) if answers else set()
    worst_b = worst_f = 0.0
    failed = 0
    matrices = {}
    for k, (key, values, rhs, x) in enumerate(answers):
        if key not in matrices:
            S = matrix(n, pattern, values)
            matrices[key] = (S, abs(S))
        S, absS = matrices[key]
        ok_shape = x is not None and np.shape(x) == (n,)
        finite = ok_shape and bool(np.all(np.isfinite(x)))
        eb = backward_error(S, absS, x, rhs) if finite else np.inf
        bad = not eb <= limits["berr"]
        worst_b = max(worst_b, eb)
        if k in picked:
            ef = forward_error(x, splu(S).solve(rhs)) if finite else np.inf
            bad = bad or not ef <= limits["fwd_err"]
            worst_f = max(worst_f, ef)
        failed += bad
    if not answers:
        worst_b = worst_f = np.inf
    checks = {"berr": {"value": worst_b, "limit": limits["berr"]},
              "fwd_err": {"value": worst_f, "limit": limits["fwd_err"]}}
    return checks, failed
