"""FEMNIST-like clients: writer styles over class prototypes."""
from __future__ import annotations

import numpy as np

from bench.generators import _pack, _prototypes, sizes_with_total


def make(seed: int, p: dict) -> dict:
    """Writer-level non-IID clients: each writer belongs to one of
    ``n_styles`` latent styles (a shared near-identity linear mix and
    shift of the class prototypes), covers ``writer_classes`` (a range,
    both ends included) of the classes, and adds its own small noise."""
    rng = np.random.default_rng([seed, 0xFE41])
    dim, C = p["dim"], p["n_classes"]
    protos = _prototypes(rng, C, dim)
    styles = []
    for _ in range(p["n_styles"]):
        M = np.eye(dim, dtype=np.float32) + np.float32(
            0.35 / np.sqrt(dim)) * rng.standard_normal((dim, dim),
                                                       dtype=np.float32)
        b = np.float32(0.9) * rng.standard_normal(dim, dtype=np.float32)
        styles.append((M, b))
    sizes = sizes_with_total(rng, p["n_clients"], p["total_samples"],
                             p["min_size"], p["max_size"], p["size_alpha"])
    style_of = rng.integers(0, p["n_styles"], p["n_clients"])
    c_lo, c_hi = p["writer_classes"]
    xs, ys = [], []
    for i, n_i in enumerate(sizes):
        M, b = styles[style_of[i]]
        cls = rng.choice(C, rng.integers(c_lo, c_hi + 1), replace=False)
        y = rng.choice(cls, n_i).astype(np.int32)
        x = protos[y] + np.float32(0.9) * rng.standard_normal(
            (n_i, dim), dtype=np.float32)
        x = x @ M.T + b + np.float32(0.1) * rng.standard_normal(
            (n_i, dim), dtype=np.float32)
        xs.append(x)
        ys.append(y)
    return _pack(xs, ys, sizes, p["test_share"], C)
