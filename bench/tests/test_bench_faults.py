"""A whole benchmark run (past the look for a chip) with the timed path
broken underneath: ``correct`` has to come out false for each fault that
a one-chip training cell can have, and true with none, pinned and
streamed. A streamed cell also produces an answer of its own, each
newcomer's eq.-9 group, and is run with that answer altered."""
import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), os.path.dirname(__file__)]

from bench import harness  # noqa: E402
from repro.core.fedgroup import FedGroupTrainer  # noqa: E402
import tiny_cell  # noqa: E402
from tiny_cell import jax_config_restored  # noqa: E402,F401


def _unchanged(ex):
    """The round returns the group state it was given."""
    def call(gp, mem, X, Y, n, keys):
        out = ex(gp, mem, X, Y, n, keys)
        glob = jax.tree_util.tree_map(lambda g: g.mean(0), gp)
        return out._replace(group_params=gp, global_params=glob)
    return call


def _half(ex):
    """Half of the cohort left out, the mean taken over the rest."""
    def call(gp, mem, X, Y, n, keys):
        h = len(n) // 2
        return ex(gp, mem[:h], X[:h], Y[:h], n[:h], keys[:h])
    return call


def _misassigned(cold_start):
    """Each round's first newcomer adopts the group after the one eq. 9
    chose for it."""
    def call(self, cold_idx):
        adopt = self._adopt_membership

        def misadopt(idx, new):
            new = np.array(new)
            new[0] = (new[0] + 1) % self.m
            adopt(idx, new)
        self._adopt_membership = misadopt
        try:
            cold_start(self, cold_idx)
        finally:
            del self._adopt_membership
    return call


@pytest.mark.usefixtures("jax_config_restored")
@pytest.mark.parametrize("streamed,fault", [
    (False, None), (False, "unchanged"), (False, "half"), (False, "altered"),
    (True, None), (True, "unchanged"), (True, "half"), (True, "altered"),
    (True, "misassigned")])
def test_broken_round_is_not_correct(monkeypatch, streamed, fault):
    orig_exec = FedGroupTrainer._round_executor
    orig_batch = FedGroupTrainer._client_batch
    orig_cold = FedGroupTrainer.client_cold_start
    if fault in ("unchanged", "half"):
        wrap = _unchanged if fault == "unchanged" else _half
        monkeypatch.setattr(FedGroupTrainer, "_round_executor",
                            lambda self: wrap(orig_exec(self)))
    elif fault == "altered":
        def batch(self, idx):
            # the first cohort client's labels shifted by one class where
            # its batch is produced: its update answers the wrong question
            x, y, n = orig_batch(self, idx)
            src = self.data if self.population is None \
                else self.population.store
            return x, y.at[0].set((y[0] + 1) % src.n_classes), n
        monkeypatch.setattr(FedGroupTrainer, "_client_batch", batch)
    elif fault == "misassigned":
        monkeypatch.setattr(FedGroupTrainer, "client_cold_start",
                            _misassigned(orig_cold))
    result, lines = harness.run("tiny", 3_000_000_001, 0.2, False,
                                time.perf_counter(), require_chip=False,
                                parts=tiny_cell.parts(streamed=streamed))
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is (fault is None), lines
    assert list(result)[-1] == "checks"
