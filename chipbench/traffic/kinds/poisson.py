"""Open-loop Poisson arrivals at ``rate_per_s``.

``round(rate * seconds)`` requests fall due over ``seconds``.  The gaps are
the exponential distribution's quantiles at (i + 0.5) / n in an order
``rng`` permutes, scaled so that the last request falls due inside the
span.  Returns the due times, from 0."""
import math

import numpy as np


def schedule(params, seconds, rng):
    n = max(1, round(params["rate_per_s"] * seconds))
    gaps = np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = gaps[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due *= seconds * (n - 0.5) / n / max(float(gaps.sum()), 1e-9)
    return due
