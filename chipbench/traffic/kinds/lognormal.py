"""Lengths from a lognormal distribution, clipped to [min, max].

The ``n`` draws are the distribution's quantiles at (i + 0.5) / n, in an
order ``rng`` permutes, so every block holds the same multiset."""
import math
from statistics import NormalDist

import numpy as np


def draw(params, n, rng):
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    mu, sigma = math.log(params["median"]), params["sigma"]
    vals = np.clip(np.rint(np.exp(mu + sigma * np.asarray(z))),
                   params["min"], params["max"]).astype(np.int64)
    return vals[rng.permutation(n)]
