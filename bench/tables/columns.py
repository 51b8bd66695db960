"""Independent categorical columns: column j takes values 0..d_j-1 with a
Zipf marginal, P(v) proportional to 1 / (v + 1) ** s.

``spec["domains"]`` lists every d_j and ``spec["zipf"]`` is s. Values are
drawn by inverting each column's cumulative distribution, so a table is
made in one pass over a uniform (rows, columns) matrix.
"""

from __future__ import annotations

import numpy as np


def generate(spec: dict, n_rows: int, rng: np.random.Generator) -> np.ndarray:
    domains = [int(d) for d in spec["domains"]]
    s = float(spec["zipf"])
    u = rng.random((n_rows, len(domains)))
    out = np.empty((n_rows, len(domains)), dtype=np.int64)
    for j, d in enumerate(domains):
        w = 1.0 / np.arange(1, d + 1) ** s
        cdf = np.cumsum(w / w.sum())
        cdf[-1] = 1.0
        out[:, j] = np.searchsorted(cdf, u[:, j], side="right")
    return out
