"""The benchmark's generators: the paper's client counts and sample
totals, fixed padded shapes, and a lazy population that is seeded."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import generators as gen  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,clients,samples,shape", [
    ("femnist_mlp512", 200, 18345, (320, 80)),
    ("mnist_mlp128", 1000, 69035, (410, 102)),
])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paper_totals(name, clients, samples, shape, seed):
    d = gen.make_data(seed, _config(name), {"feeding": "pinned"})
    assert len(d["n_train"]) == clients
    assert int(d["n_train"].sum() + d["n_test"].sum()) == samples
    assert (d["x_train"].shape[1], d["x_test"].shape[1]) == shape
    p = _config(name)["data"]
    sizes = d["n_train"] + d["n_test"]
    assert sizes.min() >= p["min_size"] and sizes.max() <= p["max_size"]


def test_sizes_with_total_exact_and_seeded():
    a = gen.sizes_with_total(np.random.default_rng(5), 37, 1234, 10, 90)
    b = gen.sizes_with_total(np.random.default_rng(5), 37, 1234, 10, 90)
    assert a.sum() == 1234 and a.min() >= 10 and a.max() <= 90
    np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError):
        gen.sizes_with_total(np.random.default_rng(0), 10, 5000, 10, 90)


def test_virtual_population_is_lazy_seeded_and_sized():
    p = _config("mnist_mlp128")["data"]
    a = gen.virtual_mnist_like(9, p, 100_000)
    b = gen.virtual_mnist_like(9, p, 100_000)
    sizes = a["n_train"] + a["n_test"]
    assert len(sizes) == 100_000 and sizes.sum() == 6_903_500
    assert sizes.min() >= p["min_size"] and sizes.max() <= p["max_size"]
    assert (a["max_train"], a["max_test"]) == (410, 102)
    for i in (0, 77_777):
        ca, cb = a["client_fn"](i), b["client_fn"](i)
        assert len(ca["y"]) == a["n_train"][i]
        assert len(ca["y_test"]) == a["n_test"][i]
        assert len(np.unique(np.concatenate([ca["y"], ca["y_test"]]))) \
            <= p["classes_per_client"]
        np.testing.assert_array_equal(ca["x"], cb["x"])
