"""The benchmark's generators: the paper's client counts and sample
totals, fixed padded shapes, and a lazy population that is seeded."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import generators as gen  # noqa: E402
from bench.data import mnist_like  # noqa: E402


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
    a = mnist_like.virtual(9, p, 100_000)
    b = mnist_like.virtual(9, p, 100_000)
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


def _digest(d, ids=()):
    """SHA-256 of a data dict's arrays (and of ``client_fn(i)`` for
    ``ids``), each with its name, dtype and shape."""
    h = hashlib.sha256()

    def put(name, a):
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{name}:{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    for k in sorted(d):
        if k != "client_fn":
            put(k, d[k])
            continue
        for i in ids:
            c = d[k](i)
            for kk in sorted(c):
                put(f"{i}.{kk}", c[kk])
    return h.hexdigest()


@pytest.mark.parametrize("name,population,ids,digest", [
    ("femnist_mlp512", None, (),
     "25a7df1896b4993a20caec63e7b5916adde558dc1ccd9c3a88dda114267e90c8"),
    ("mnist_mlp128", None, (),
     "0374ec7f2643be8f0eb832d7ce088bfcdb8507ba95f701781022235323dd375a"),
    ("mnist_mlp128", 2000, (0, 1, 777, 1999),
     "d4d33686f0c7a2a75a437019584b428ff51d4d42fdf127f870269f668db7a3ed"),
    ("mnist_mlp128", 100_000, (0, 77_777, 99_999),
     "671241ea511b1be1981cdc2552b6d1469a2f28b212f466a2f90a0b459fb4560c"),
])
def test_a_seed_gives_the_recorded_arrays(name, population, ids, digest):
    """The generators' output on one large seed, pinned and as a streamed
    population, is held bit for bit to recorded digests: a cell's inputs
    do not move when the code that makes them is reorganised."""
    traffic = ({"feeding": "pinned"} if population is None else
               {"feeding": "population", "population": population})
    d = gen.make_data(2_147_483_659, _config(name), traffic)
    assert _digest(d, ids) == digest
