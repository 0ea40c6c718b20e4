"""Arithmetic the yardstick rests on: percentiles, spreads, and the fixed
sets of sizes a traffic mix is made of.

A mix gives every seed the SAME multiset of sizes and gaps and lets the seed
choose only their order (and the token ids): two runs then differ by order
and by noise, not by how much work they were handed.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile (the arithmetic of
    ``llmd_tpu/benchmark/analysis.py::_pct``); None for no samples."""
    if len(values) == 0:
        return None
    s = sorted(values)
    k = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return float(s[k])


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median with ``statistics.quantiles(values, n=4)`` — the
    spread the benchmark's bounds are set from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def _lognormal_params(mean: float, sd: float) -> tuple[float, float]:
    # As llmd_tpu/benchmark/workload.py::Distribution.sample: the lognormal
    # whose own mean and standard deviation are the ones given.
    sigma2 = math.log(1.0 + (sd * sd) / (mean * mean))
    return math.log(mean) - sigma2 / 2.0, math.sqrt(sigma2)


def size_grid(dist: dict, n: int) -> np.ndarray:
    """``n`` whole sizes at the mid-quantiles (i + 0.5) / n of ``dist``:
    ``{"type": "lognormal", "mean", "sd", "min", "max"}``,
    ``{"type": "uniform", "min", "max"}`` or ``{"type": "constant",
    "value"}``. Deterministic: the seed only orders them."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["type"]
    if kind == "constant":
        v = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        mu, sigma = _lognormal_params(float(dist["mean"]), float(dist["sd"]))
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = np.exp(mu + sigma * z)
    else:
        raise ValueError(f"unknown distribution type {kind!r}")
    if "min" in dist:
        v = np.maximum(v, dist["min"])
    if "max" in dist:
        v = np.minimum(v, dist["max"])
    return np.rint(v).astype(np.int64)


def gap_grid(n: int, total: float, cv: float = 1.0) -> np.ndarray:
    """``n`` inter-arrival gaps summing to ``total`` seconds, at the
    mid-quantiles of a gamma distribution with coefficient of variation
    ``cv`` (cv 1 is the exponential of Poisson arrivals)."""
    u = (np.arange(n) + 0.5) / n
    if abs(cv - 1.0) < 1e-9:
        g = -np.log1p(-u)
    else:
        # Quantiles of gamma(shape k = 1/cv^2) by sampling its own grid: a
        # large fixed-seed sample, sorted, read at the mid-quantiles.
        k = 1.0 / (cv * cv)
        s = np.sort(np.random.default_rng(0).gamma(k, 1.0, size=200_003))
        g = s[(u * len(s)).astype(np.int64)]
    return g * (total / g.sum())


def zipf_counts(n: int, groups: int, s: float = 1.0) -> np.ndarray:
    """How many of ``n`` draws fall on each of ``groups`` ranks under a Zipf
    law with exponent ``s`` (largest-remainder rounding, sums to n)."""
    w = 1.0 / np.arange(1, groups + 1) ** s
    exact = n * w / w.sum()
    base = np.floor(exact).astype(np.int64)
    rest = n - int(base.sum())
    order = np.argsort(-(exact - base), kind="stable")
    base[order[:rest]] += 1
    return base
