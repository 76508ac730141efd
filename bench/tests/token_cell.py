"""A model kind and a data generator that the benchmark does not ship,
for the tests that a kind is added as modules alone. The test places this
module under ``bench.models.<KIND>`` and ``bench.data.<KIND>``.

Rows are ``seq_len`` integer tokens (held as float32, as the client
stores hold rows); each row's label is its class, whose token
distribution the row was drawn from. One label a row, not a token: the
program's client stores and its client loss hold one label a row
(``fed/store.py``, ``fed/client.py:client_mean_loss``); the reference's
loss and count over a label a token are tested on their own. The parameters are a nested tree,
``{"layers": [{"w", "b"}, {"w", "b"}]}``: a token embedding averaged over
the row, then a linear classifier of its ReLU. The list sits under a key:
the program reads a parameter tree whose top level is a list as a list of
groups (``fed/server.py:tree_index``)."""
import copy

import jax
import jax.numpy as jnp
import numpy as np

from bench.generators import _pack, sizes_with_total
import tiny_cell

KIND = "test_tokens"
MODEL = {"kind": KIND, "vocab": 12, "width": 8, "n_classes": 4,
         "d_w": 12 * 8 + 8 + 8 * 4 + 4}
DATA = {"generator": KIND, "n_clients": 12, "total_samples": 400,
        "seq_len": 6, "vocab": 12, "n_classes": 4, "classes_per_client": 2,
        "min_size": 10, "max_size": 60, "size_alpha": 1.5,
        "test_share": 0.2}


# -- the kind --------------------------------------------------------------
def program(model, data):
    from repro.models.paper_models import ModelSpec

    def init(key):
        k1, k2 = jax.random.split(key)
        v, w, c = model["vocab"], model["width"], model["n_classes"]
        return {"layers": [
            {"w": jax.random.normal(k1, (v, w)), "b": jnp.zeros((w,))},
            {"w": jax.random.normal(k2, (w, c)) * (1.0 / w) ** 0.5,
             "b": jnp.zeros((c,))}]}

    return ModelSpec(KIND, init, apply)


def row_shape(model, data):
    return (data["seq_len"],)


def apply(p, x):
    embed, out = p["layers"]
    e = embed["w"][x.astype(jnp.int32)]              # (..., seq, width)
    h = jax.nn.relu(jnp.mean(e, axis=-2) + embed["b"])
    return h @ out["w"] + out["b"]


def train_flops_per_sample(model, data):
    """The classifier's product forward, its weight gradient and the
    gradient into the pooled row; the embedding is a lookup."""
    return 3 * 2 * model["width"] * model["n_classes"]


# -- the generator -----------------------------------------------------------
def _client(rng, n, p, token_p):
    cls = rng.choice(p["n_classes"], p["classes_per_client"], replace=False)
    y = rng.choice(cls, n).astype(np.int32)
    x = np.stack([rng.choice(p["vocab"], p["seq_len"], p=token_p[c])
                  for c in y]).astype(np.float32)
    return x, y


def _token_p(rng, p):
    return rng.dirichlet(np.full(p["vocab"], 0.3), p["n_classes"])


def make(seed, p):
    rng = np.random.default_rng([seed, 0x70C])
    token_p = _token_p(rng, p)
    sizes = sizes_with_total(rng, p["n_clients"], p["total_samples"],
                             p["min_size"], p["max_size"], p["size_alpha"])
    xs, ys = zip(*(_client(rng, int(n), p, token_p) for n in sizes))
    return _pack(xs, ys, sizes, p["test_share"], p["n_classes"])


def virtual(seed, p, n_clients):
    rng = np.random.default_rng([seed, 0x70C])
    token_p = _token_p(rng, p)
    total = round(p["total_samples"] * n_clients / p["n_clients"])
    sizes = sizes_with_total(rng, n_clients, total, p["min_size"],
                             p["max_size"], p["size_alpha"])
    n_test = np.maximum(1, (sizes * p["test_share"]).astype(np.int64))
    n_train = sizes - n_test

    def client_fn(i):
        r = np.random.default_rng([seed, 0x70D, int(i)])
        x, y = _client(r, int(sizes[i]), p, token_p)
        te = int(n_test[i])
        return {"x": x[te:], "y": y[te:], "x_test": x[:te], "y_test": y[:te]}

    return {"n_train": n_train.astype(np.int32),
            "n_test": n_test.astype(np.int32), "n_classes": p["n_classes"],
            "max_train": int(n_train.max()), "max_test": int(n_test.max()),
            "client_fn": client_fn}


def parts(streamed: bool = False) -> dict:
    """``tiny_cell.parts`` with this kind's model and data."""
    out = tiny_cell.parts(streamed=streamed)
    out["config"] = dict(copy.deepcopy(out["config"]), name="tiny_tokens",
                         model=dict(MODEL), data=dict(DATA))
    return out
