"""The benchmark's own generators of client data.

Everything here is a pure function of a seed and the parameter dict of
``bench/configs/<config>.json``, so the same seed gives the same inputs on
every run and on both sides of a comparison. Nothing here imports the
program.

Data. The paper's FEMNIST and MNIST sets are not in the checkout; the
generators make synthetic clients with the structure the paper
manipulates (class prototypes, writer styles, label skew, power-law
client sizes) and with the paper's client counts and sample totals.
Client sizes are power-law draws clipped to ``[min_size, max_size]`` and
then scaled so that they sum to the configured total exactly.

Each data generator returns a dict of padded numpy arrays:
``x_train (N, max_train, dim)``, ``y_train (N, max_train)``,
``n_train (N,)`` and the same for ``test``, plus ``n_classes``.
"""
from __future__ import annotations

import numpy as np


def sizes_with_total(rng: np.random.Generator, n: int, total: int,
                     lo: int, hi: int, alpha: float = 1.5) -> np.ndarray:
    """``n`` power-law client sizes in ``[lo, hi]`` that sum to ``total``.

    The draw is ``pareto(alpha) + 1``; the scale is found by bisection so
    that the clipped sizes sum to the total, and the rounding remainder
    goes one sample at a time to the clients with the largest fractional
    parts that are still below ``hi``."""
    if not n * lo <= total <= n * hi:
        raise ValueError(f"{n} clients of {lo}..{hi} samples cannot hold "
                         f"{total}")
    w = rng.pareto(alpha, n) + 1.0
    w = w / w.sum()
    a, b = 0.0, float(total) * n
    for _ in range(200):
        s = 0.5 * (a + b)
        if np.clip(s * w, lo, hi).sum() < total:
            a = s
        else:
            b = s
    exact = np.clip(a * w, lo, hi)
    sizes = np.floor(exact).astype(np.int64)
    short = int(total - sizes.sum())
    order = np.argsort(-(exact - sizes), kind="stable")
    for i in order:
        if short == 0:
            break
        if sizes[i] < hi:
            sizes[i] += 1
            short -= 1
    if short != 0 or sizes.sum() != total:
        raise ValueError(f"could not place {total} samples in {n} clients")
    return sizes


def _prototypes(rng, n_classes: int, dim: int, sep: float = 2.2):
    protos = rng.standard_normal((n_classes, dim), dtype=np.float32)
    protos /= np.linalg.norm(protos, axis=1, keepdims=True)
    return protos * np.float32(sep)


def _pack(xs, ys, sizes, test_share: float, n_classes: int) -> dict:
    """Split each client's samples into test (first ``test_share``) and
    train, and pad both into stacked arrays."""
    n_test = np.maximum(1, (sizes * test_share).astype(np.int64))
    n_train = sizes - n_test
    N, dim = len(sizes), xs[0].shape[1]
    out = {"x_train": np.zeros((N, int(n_train.max()), dim), np.float32),
           "y_train": np.zeros((N, int(n_train.max())), np.int32),
           "x_test": np.zeros((N, int(n_test.max()), dim), np.float32),
           "y_test": np.zeros((N, int(n_test.max())), np.int32),
           "n_train": n_train.astype(np.int32),
           "n_test": n_test.astype(np.int32), "n_classes": int(n_classes)}
    for i, (x, y) in enumerate(zip(xs, ys)):
        te, tr = int(n_test[i]), int(n_train[i])
        out["x_test"][i, :te], out["y_test"][i, :te] = x[:te], y[:te]
        out["x_train"][i, :tr], out["y_train"][i, :tr] = x[te:], y[te:]
    return out


def femnist_like(seed: int, p: dict) -> dict:
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


def mnist_like(seed: int, p: dict) -> dict:
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


def virtual_mnist_like(seed: int, p: dict, n_clients: int) -> dict:
    """``mnist_like`` clients for a population of ``n_clients``, made one
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


DATA = {"femnist_like": femnist_like, "mnist_like": mnist_like}
VIRTUAL = {"mnist_like": virtual_mnist_like}


def make_data(seed: int, config: dict, traffic: dict) -> dict:
    """The client data of a configuration under a traffic mix: padded
    arrays for pinned feeding, a lazy population of the traffic's size
    for streamed feeding."""
    p = config["data"]
    if traffic["feeding"] == "population":
        return VIRTUAL[p["generator"]](seed, p, int(traffic["population"]))
    return DATA[p["generator"]](seed, p)
