"""MNIST-like clients: label-skewed class clusters, pinned or as a lazy
population."""
from __future__ import annotations

import numpy as np

from bench.generators import _pack, _prototypes, sizes_with_total


def make(seed: int, p: dict) -> dict:
    """Label-skewed class-cluster clients: each client holds
    ``classes_per_client`` classes in near-equal shares; a sample is its
    class prototype plus unit Gaussian noise."""
    rng = np.random.default_rng([seed, 0x3417])
    dim, C, k = p["dim"], p["n_classes"], p["classes_per_client"]
    protos = _prototypes(rng, C, dim)
    sizes = sizes_with_total(rng, p["n_clients"], p["total_samples"],
                             p["min_size"], p["max_size"], p["size_alpha"])
    xs, ys = [], []
    for n_i in sizes:
        cls = rng.choice(C, k, replace=False)
        y = np.repeat(cls, [n_i // k + (j < n_i % k) for j in range(k)])
        y = rng.permutation(y).astype(np.int32)
        xs.append(protos[y] + rng.standard_normal((n_i, dim),
                                                  dtype=np.float32))
        ys.append(y)
    return _pack(xs, ys, sizes, p["test_share"], C)


def virtual(seed: int, p: dict, n_clients: int) -> dict:
    """``make``'s clients for a population of ``n_clients``, made one
    at a time on first touch: the size table (the configuration's sizes
    scaled to ``n_clients`` at the same mean) is all that exists up front,
    and client ``i``'s samples come from its own seed ``[seed, tag, i]``.
    -> the size table, padded widths and ``client_fn(i)`` returning the
    unpadded ``x, y, x_test, y_test`` a ``VirtualClientStore`` takes."""
    rng = np.random.default_rng([seed, 0x3417])
    dim, C, k = p["dim"], p["n_classes"], p["classes_per_client"]
    protos = _prototypes(rng, C, dim)
    total = round(p["total_samples"] * n_clients / p["n_clients"])
    sizes = sizes_with_total(rng, n_clients, total, p["min_size"],
                             p["max_size"], p["size_alpha"])
    n_test = np.maximum(1, (sizes * p["test_share"]).astype(np.int64))
    n_train = sizes - n_test

    def client_fn(i: int) -> dict:
        r = np.random.default_rng([seed, 0x7C11, int(i)])
        n_i, te = int(sizes[i]), int(n_test[i])
        cls = r.choice(C, k, replace=False)
        y = np.repeat(cls, [n_i // k + (j < n_i % k) for j in range(k)])
        y = r.permutation(y).astype(np.int32)
        x = protos[y] + r.standard_normal((n_i, dim), dtype=np.float32)
        return {"x": x[te:], "y": y[te:], "x_test": x[:te], "y_test": y[:te]}

    return {"n_train": n_train.astype(np.int32),
            "n_test": n_test.astype(np.int32), "n_classes": int(C),
            "max_train": int(n_train.max()), "max_test": int(n_test.max()),
            "client_fn": client_fn}
