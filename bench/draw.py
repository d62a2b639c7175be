"""Fixed sets of sizes and gaps, drawn by quantile from a traffic file's
distributions, so that every seed sends the same work in another order."""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` values at the mid-quantiles ``(i + 0.5) / n`` of ``spec``:

    {"dist": "uniform", "min": a, "max": b}
    {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
    {"dist": "pareto", "alpha": k, "min": a, "max": b}
    {"dist": "exponential", "mean": m}

    Values are clipped to [min, max] and rounded to integers unless the
    spec says ``"float": true``."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
        if not spec.get("float"):
            # equal counts of every integer in [min, max]
            x = np.floor(spec["min"] + q * (spec["max"] - spec["min"] + 1))
    elif dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(v) for v in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "pareto":
        x = spec["min"] * (1.0 - q) ** (-1.0 / spec["alpha"])
    elif dist == "exponential":
        x = -spec["mean"] * np.log1p(-q)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in spec:
        x = np.clip(x, spec["min"], spec["max"])
    if spec.get("float"):
        return x.astype(np.float64)
    return np.rint(x).astype(np.int64)


def pow2_bucket(n: int, floor: int) -> int:
    """The engine's dispatch length for ``n`` tokens: the least power of two
    at or above both."""
    b = max(int(floor), 1)
    while b < n:
        b <<= 1
    return b
