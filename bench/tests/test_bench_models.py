"""Model kinds found by name: the MLP's operation count by hand, a kind
that the benchmark does not ship run through the whole harness, pinned
and streamed, against its reference and its bf16 control, and the
reference's loss and count over labels of any trailing shape."""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), os.path.dirname(__file__)]

from bench import compare, harness, reference  # noqa: E402
from bench import generators as gen  # noqa: E402
from bench.models import mlp  # noqa: E402
import token_cell  # noqa: E402
from tiny_cell import jax_config_restored  # noqa: E402,F401

SEED = 2_147_483_761


@pytest.mark.parametrize("name,per_sample", [("femnist_mlp512", 1_685_504),
                                             ("mnist_mlp128", 409_088)])
def test_mlp_per_sample_by_hand(name, per_sample):
    # forward 2*(d*h + h*c); backward dW1 2*d*h, dW2 2*h*c, dh 2*h*c
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    m = cfg["model"]
    d, h, c = m["in_dim"], m["hidden"], m["n_classes"]
    fwd = 2 * d * h + 2 * h * c
    bwd = 2 * d * h + 2 * h * c + 2 * h * c
    assert mlp.train_flops_per_sample(m, cfg["data"]) == fwd + bwd
    assert fwd + bwd == per_sample


@pytest.fixture
def token_kind(monkeypatch):
    """The test's kind and generator, placed where the harness looks."""
    for pkg in ("bench.models", "bench.data"):
        monkeypatch.setitem(sys.modules, f"{pkg}.{token_cell.KIND}",
                            token_cell)


@pytest.mark.usefixtures("jax_config_restored", "token_kind")
@pytest.mark.parametrize("streamed", [False, True])
def test_a_new_kind_runs_through_the_harness(streamed):
    result, lines = harness.run("tiny_tokens", SEED, 0.2, False,
                                time.perf_counter(), require_chip=False,
                                parts=token_cell.parts(streamed))
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is True, lines
    assert ("assign_gap" in result["checks"]) == streamed


@pytest.mark.usefixtures("token_kind")
@pytest.mark.parametrize("streamed", [False, True])
def test_a_new_kind_fails_its_bf16_control(streamed):
    parts = token_cell.parts(streamed)
    config = parts["config"]
    data = gen.make_data(SEED, config, parts["traffic"])
    tr = harness.build_trainer(config, parts["traffic"], SEED, data)
    assert [set(layer) for layer in tr.params["layers"]] == [{"w", "b"}] * 2
    feed = harness.Feed(tr)
    tr.group_cold_start()
    start, prog, cohorts = harness.check_rounds(tr, feed, data, 3)
    ids = harness.eval_ids(tr)
    tr.close()
    ref = harness.reference_rounds(data, config, start, cohorts, prog, ids)
    ok, checks = compare.verdict(compare.numbers(start, prog, ref),
                                 parts["limits"])
    assert ok, checks
    low = harness.reference_rounds(data, config, start, cohorts, prog, ids,
                                   dtype=jnp.bfloat16)
    for a, p in zip(low, prog):
        a["n_test"] = p["n_test"]
    ok, checks = compare.verdict(compare.numbers(start, low, ref),
                                 parts["limits"])
    assert not ok, checks


def _per_token(p, x):
    return x @ p["w"]


def test_reference_takes_one_label_a_token():
    """Labels ``(rows, seq)`` and logits ``(rows, seq, classes)``: the loss
    is the mean over the valid rows' tokens, the count over their
    tokens."""
    rng = np.random.default_rng(3)
    p = {"w": rng.standard_normal((5, 4)).astype(np.float32)}
    x = rng.standard_normal((6, 3, 5)).astype(np.float32)
    y = rng.integers(0, 4, (6, 3)).astype(np.int32)
    n = 4
    logits = np.einsum("rsd,dc->rsc", x.astype(np.float64), p["w"])
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    ce = -np.take_along_axis(logp, y[..., None], -1)[..., 0]
    loss = reference.client_loss(p, x, y, n, apply=_per_token)
    np.testing.assert_allclose(float(loss), ce[:n].mean(), rtol=1e-5)
    right = (logits.argmax(-1) == y)[:n].sum()
    assert int(reference.client_correct(p, x, y, n, apply=_per_token)) \
        == right
    batch = reference.batch_loss(_per_token, p, x, y)
    np.testing.assert_allclose(float(batch), ce.mean(), rtol=1e-5)


def test_leaf_norms_walk_a_nested_tree():
    before = {"groups": [{"w": np.zeros((2, 3)), "b": np.zeros((2,))}],
              "glob": [{"w": np.zeros(3), "b": np.zeros(())}]}
    after = jax.tree_util.tree_map(lambda v: v + 1.0, before)
    norms = compare.leaf_norms(after, before)
    assert norms == {"group0[0]['b']": 1.0, "group1[0]['b']": 1.0,
                     "group0[0]['w']": 3 ** 0.5, "group1[0]['w']": 3 ** 0.5,
                     "global[0]['b']": 1.0, "global[0]['w']": 3 ** 0.5}
