"""FeSEM (Xie et al. 2020, "Multi-Center Federated Learning").

ℓ2-distance stochastic EM: the server keeps m centers; each participating
client is assigned (E-step) to the center minimizing ||w_i − w_g||₂ between
its *local model* and the center, trains from that center, and centers are
recomputed (M-step) as weighted averages of their members' local models.

The ℓ2 distance on flattened HDLSS parameters is exactly what the paper's
EDC measure is designed to beat (distance concentration, §2.2).

Both EM halves are fused into the round executor's single dispatch: the
E-step is the in-program assignment stage (``make_fesem_assign``) over
flattened centers, and the M-step is the executor's intra-group FedAvg
(center + avg_w(Δ) ≡ avg_w of the members' final local models). The
per-client flattened-model matrix ``local_flat`` is a persistent device
array updated by an in-program scatter (``fesem_state_update``) — the seed
implementation's host numpy matrix rebuilt through ``_flat()`` round-trips
every round survives only as ``fed.rounds.serial_fesem_round``.

In ``population=`` mode the (N, d_w) matrix stays host-resident in the
``ClientStateTable`` (lazy rows); each round gathers only the cohort's
(K, d_w) rows to device, runs the *same* compiled round with cohort-local
ids, and scatters the updated rows back — dynamic assignment keeps working
when the population no longer fits on device. The write-back goes through
``Population.scatter_local_flat``: split per data shard and applied on a
background writer thread (drained before any gather), so on a 2-D
``(data, model)`` mesh each simulated host scatters only its cohort
slice — see docs/scaling.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed import rounds as rounds_lib
from repro.fed.engine import FedConfig, GroupedTrainer, RoundMetrics
from repro.models.modules import flatten_updates


def make_fesem_assign():
    """Assignment stage: argmin-ℓ2 E-step of each selected client's last
    local model against the flattened group centers. state:
    {"local_flat": (n_clients, d_w), "idx": (K,) selected client ids}."""
    def assign(group_params, X, Y, n, state):
        centers = jax.vmap(flatten_updates)(group_params)       # (m, d_w)
        local = state["local_flat"][state["idx"]]               # (K, d_w)
        d2 = jnp.sum(jnp.square(local[:, None, :] - centers[None]), -1)
        return jnp.argmin(d2, axis=1)

    return assign


def fesem_state_update(state, membership, deltas, finals):
    """Scatter the selected clients' new flattened local models back into
    the persistent (n_clients, d_w) device matrix — no host round-trip."""
    flat = jax.vmap(flatten_updates)(finals)                    # (K, d_w)
    return {"idx": state["idx"],
            "local_flat": state["local_flat"].at[state["idx"]].set(flat)}


class FeSEMTrainer(GroupedTrainer):
    framework = "fesem"

    def __init__(self, model, data, cfg: FedConfig, mesh=None,
                 population=None):
        super().__init__(model, data, cfg, mesh=mesh, population=population)
        keys = jax.random.split(jax.random.PRNGKey(cfg.seed + 29), self.m)
        self.group_params = rounds_lib.stack_trees(
            [model.init(k) for k in keys])
        # local models last seen per client, initialized to center 0
        flat0 = flatten_updates(self.group_param(0))
        if population is not None:
            # population scale: the (N, d_w) matrix stays HOST-resident in
            # the state table (lazy rows, default = init center 0); only
            # the cohort's (K, d_w) rows are gathered to device per round
            self.local_flat = None
            population.state.init_local_flat(np.asarray(flat0))
        else:
            # pinned: lives on device for the in-program E-step gather /
            # M-step scatter
            self.local_flat = jnp.tile(flat0[None], (self.n_clients, 1))

    def _exec_spec(self) -> dict:
        return {"n_groups": self.m, "eta_g": 0.0,
                "assign_fn": make_fesem_assign(),
                "state_update_fn": fesem_state_update}

    # -- round-block carry: the (N, d_w) local-model matrix rides along ----
    def _block_kwargs(self) -> dict:
        kw = dict(self._exec_spec())
        # per-step E-step state from the carried matrix (idx already
        # redirected to the trash row for zero-weight padded lanes), and
        # the updated matrix back out of the M-step scatter
        kw["make_state"] = lambda aux, idx, mem: {"local_flat": aux,
                                                  "idx": idx}
        kw["state_to_aux"] = lambda st: st["local_flat"]
        return kw

    def _carry_aux(self):
        d_w = self.local_flat.shape[1]
        return jnp.concatenate(
            [self.local_flat, jnp.zeros((1, d_w), self.local_flat.dtype)])

    def _carry_refs(self, carry: dict):
        super()._carry_refs(carry)
        if carry["aux"] is not None:
            self.local_flat = carry["aux"][:-1]

    # -- async streaming: the E-step state rides each staged dispatch ------
    def _async_stream_arg(self, idx):
        # stage-time gather (drains the async writer, so every earlier
        # fold's scatter is visible) — the rows a real async client would
        # have trained from at dispatch time
        rows = jnp.asarray(self.population.gather_local_flat(idx))
        return {"local_flat": rows,
                "idx": jnp.arange(len(idx), dtype=jnp.int32)}

    def _async_adopt(self, out, idx, folded_groups, folded_global):
        super()._async_adopt(out, idx, folded_groups, folded_global)
        self.population.scatter_local_flat(
            idx, np.asarray(out.assign_state["local_flat"]))

    def round(self, t: int, idx=None) -> RoundMetrics:
        with self.obs.span("round", t=t):
            if idx is None:
                idx = self._select()
            # FeSEM: server-side E-step, then 1 center down + 1 model up
            self.comm_params += 2 * len(idx) * self.model_size
            x, y, n, keys = self._stage_cohort(idx)
            if self.population is not None:
                # state-table gather: cohort rows with cohort-local ids —
                # the executor program is byte-identical to the pinned
                # one, the E-step gather/M-step scatter just act on
                # (K, d_w) instead of the full (N, d_w). The population
                # gather drains the async writer first, so last round's
                # per-shard scatters are visible.
                rows = jnp.asarray(self.population.gather_local_flat(idx))
                state = {"local_flat": rows,
                         "idx": jnp.arange(len(idx), dtype=jnp.int32)}
            else:
                state = {"local_flat": self.local_flat,
                         "idx": jnp.asarray(np.asarray(idx, np.int32))}
            out = self._round_executor()(self.group_params, state,
                                         x, y, n, keys)
            with self.obs.span("fold"):
                self.group_params = out.group_params
                with self.obs.span("sync"):
                    mem = np.asarray(out.membership)
                    if self.population is not None:
                        rows = np.asarray(out.assign_state["local_flat"])
                if self.population is not None:
                    # async per-shard write-back: overlaps evaluation + the
                    # next cohort's H2D; the next gather_local_flat drains
                    # it first
                    self.population.scatter_local_flat(idx, rows)
                else:
                    self.local_flat = out.assign_state["local_flat"]
                self._adopt_membership(idx, mem)
                return self._fold_round(t, out, idx)

    # -- checkpointing: + the pinned (N, d_w) local-model matrix ------------
    # (population mode keeps the rows host-resident in the state table,
    # which checkpoints itself via Population.ckpt_state)
    def _ckpt_model_tree(self) -> dict:
        tree = super()._ckpt_model_tree()
        if self.local_flat is not None:
            tree["local_flat"] = self.local_flat
        return tree

    def _ckpt_load_model(self, tree: dict):
        super()._ckpt_load_model(tree)
        if "local_flat" in tree:
            self.local_flat = tree["local_flat"]
