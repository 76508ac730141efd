"""IFCA (Ghosh et al., NeurIPS 2020) — the strongest CFL baseline.

Per round the server broadcasts ALL m cluster models to the selected clients;
each client estimates its cluster identity as the model with minimum local
training loss, then optimizes that model. Accurate but communication-heavy
(m× model broadcast per round — the overhead FedGroup's static grouping and
newcomer cold start avoid; we count it in the benchmark).

The argmin-loss estimation runs as the round executor's in-program
assignment stage (``make_ifca_assign``): the per-client loss under all m
stacked group models and the subsequent per-cluster FedAvg are fused into
ONE device dispatch per round — the retired estimate-then-loop baseline
survives as ``fed.rounds.serial_ifca_round``. Fusion changes only the
dispatch count; the m× broadcast *communication accounting* is exactly the
seed's ((m+1) model transfers per selected client per round).

On a mesh the fused assignment rides the executor's placement unchanged:
the per-client losses shard over the data axes with the cohort, and on a
2-D ``(data, model)`` mesh the m stacked models' parameter dim shards
over "model" (docs/scaling.md) — the argmin still runs in-program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed import client as client_lib
from repro.fed import rounds as rounds_lib
from repro.fed.engine import FedConfig, GroupedTrainer, RoundMetrics


def make_ifca_assign(model):
    """Assignment stage: per-client argmin of mean train loss over the m
    stacked group models (IFCA §3 cluster-identity estimate)."""
    loss_one = client_lib.client_mean_loss(model)

    def assign(group_params, X, Y, n, state):
        per_client = jax.vmap(loss_one, in_axes=(None, 0, 0, 0))
        losses = jax.vmap(lambda gp: per_client(gp, X, Y, n))(group_params)
        return jnp.argmin(losses, axis=0)                   # (K,) over m

    return assign


class IFCATrainer(GroupedTrainer):
    framework = "ifca"

    def __init__(self, model, data, cfg: FedConfig, mesh=None,
                 population=None):
        super().__init__(model, data, cfg, mesh=mesh, population=population)
        keys = jax.random.split(jax.random.PRNGKey(cfg.seed + 17), self.m)
        # random initializations of cluster centers (IFCA §3)
        self.group_params = rounds_lib.stack_trees(
            [model.init(k) for k in keys])
        self.comm_models_per_round = self.m  # broadcast overhead bookkeeping

    def _exec_spec(self) -> dict:
        return {"n_groups": self.m, "eta_g": 0.0,
                "assign_fn": make_ifca_assign(self.model)}

    def _stage_comm(self, k: int):
        # the m× broadcast accounting is per ALIVE client, block or not
        self.comm_params += (self.m + 1) * k * self.model_size

    def _async_stream_arg(self, idx):
        return None      # the in-program argmin-loss stage needs no state

    def round(self, t: int, idx=None) -> RoundMetrics:
        with self.obs.span("round", t=t):
            if idx is None:
                idx = self._select()
            # IFCA broadcasts ALL m cluster models to every selected client
            self.comm_params += (self.m + 1) * len(idx) * self.model_size
            x, y, n, keys = self._stage_cohort(idx)
            out = self._round_executor()(self.group_params, None,
                                         x, y, n, keys)
            with self.obs.span("fold"):
                self.group_params = out.group_params
                with self.obs.span("sync"):
                    mem = np.asarray(out.membership)
                # persists into the population state table when streaming
                # (the trainer's membership array IS the table's column);
                # migrations are counted into the telemetry registry on
                # the way through
                self._adopt_membership(idx, mem)
                return self._fold_round(t, out, idx)
