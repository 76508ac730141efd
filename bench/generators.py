"""The benchmark's own generators of client data: ``make_data`` and the
helpers the generators share. Each generator is a module of its own,
``bench/data/<generator>.py``, named by ``config["data"]["generator"]``.

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

A generator's ``make(seed, p)`` returns a dict of padded numpy arrays:
``x_train (N, max_train, *row)``, ``y_train (N, max_train, *labels)``,
``n_train (N,)`` and the same for ``test``, plus ``n_classes``. Its
``virtual(seed, p, n_clients)``, where it streams, returns the size
tables ``n_train``, ``n_test``, the padded widths ``max_train``,
``max_test``, ``n_classes`` and ``client_fn(i)`` -> client ``i``'s
unpadded ``x, y, x_test, y_test``.
"""
from __future__ import annotations

import importlib

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


def make_data(seed: int, config: dict, traffic: dict) -> dict:
    """The client data of a configuration under a traffic mix: padded
    arrays for pinned feeding, a lazy population of the traffic's size
    for streamed feeding."""
    p = config["data"]
    gen = importlib.import_module(f"bench.data.{p['generator']}")
    if traffic["feeding"] == "population":
        return gen.virtual(seed, p, int(traffic["population"]))
    return gen.make(seed, p)
