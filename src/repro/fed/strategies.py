"""Pluggable assignment-strategy zoo behind the fused round's
``assign_fn``/``state_update_fn`` stage.

The round executor (``fed.rounds``) already runs IFCA's argmin-loss and
FeSEM's argmin-ℓ2 cluster estimation *inside* the compiled round; this
module turns that stage into a registry of strategies and adds two more
measures from the follow-up literature — both sharing the same compiled
fused round, no new dispatches:

  fedclust  partial-weight cosine similarity (FedClust, arXiv 2403.04144):
            each client is assigned to the group whose flattened center is
            most cosine-similar on the *trailing* ``d_head`` coordinates of
            the flattened weights (the classifier head under the repo's
            flatten order — the layer FedClust finds most label-skew
            sensitive). Rides FeSEM's persistent per-client ``local_flat``
            state (E-step gather / M-step scatter) unchanged.
  lcfl      local-loss assignment with hysteresis (LCFL, arXiv
            2407.09360): per-client loss under all m stacked models like
            IFCA, but a client *keeps* its current group unless a rival
            beats it by more than a multiplicative ``margin`` — loss-driven
            clustering without IFCA's churn near decision boundaries. The
            assignment state is the cohort's current membership row, so
            the strategy is stateful but carries nothing new.

Every strategy registers a :class:`StrategySpec`; the registry is the
single source the tests iterate for the generic invariance properties
(permutation equivariance over clients, group-relabel invariance) and the
serial-oracle equivalence checks:

>>> from repro.fed import strategies
>>> strategies.available_strategies()
['fedclust', 'fesem', 'ifca', 'lcfl', 'static']
>>> strategies.get_strategy('lcfl').state_kind
'membership'

Serial host references (``serial_fedclust_round`` / ``serial_lcfl_round``)
mirror ``fed.rounds.serial_ifca_round`` / ``serial_fesem_round``: numpy
assignment + the retired per-group solver loop, kept as the equivalence
oracles for tests/test_strategies.py.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import measures
from repro.fed import client as client_lib
from repro.fed import rounds as rounds_lib
from repro.fed.engine import FedConfig, GroupedTrainer, RoundMetrics
from repro.fed.fesem import FeSEMTrainer, fesem_state_update
from repro.models.modules import flatten_updates


# ---------------------------------------------------------------------------
# FedClust: partial-weight cosine similarity
# ---------------------------------------------------------------------------
def fedclust_head_dim(d_w: int, frac: float) -> int:
    """Static head width: the trailing ``frac`` of the ``d_w`` flattened
    coordinates, at least 1 (``FedConfig.fedclust_frac``)."""
    return max(1, min(int(d_w), int(float(frac) * int(d_w))))


def make_fedclust_assign(d_head: int):
    """Assignment stage: argmax cosine similarity between each selected
    client's local model and the group centers, compared on the trailing
    ``d_head`` flattened coordinates only. Same state as FeSEM:
    {"local_flat": (n_clients, d_w), "idx": (K,) selected client ids}."""
    def assign(group_params, X, Y, n, state):
        centers = jax.vmap(flatten_updates)(group_params)   # (m, d_w)
        local = state["local_flat"][state["idx"]]           # (K, d_w)
        sim = measures.cosine_similarity_matrix(
            local[:, -d_head:], centers[:, -d_head:])       # (K, m)
        return jnp.argmax(sim, axis=1)

    return assign


def serial_fedclust_assign(centers, local_flat, d_head: int) -> np.ndarray:
    """Host numpy oracle of ``make_fedclust_assign``: row-normalized
    (epsilon-guarded, exactly ``measures.row_normalize``) trailing-head
    cosine argmax."""
    c = np.asarray(centers, np.float32)[:, -d_head:]
    l = np.asarray(local_flat, np.float32)[:, -d_head:]
    cn = c / np.maximum(np.linalg.norm(c, axis=1, keepdims=True), 1e-12)
    ln = l / np.maximum(np.linalg.norm(l, axis=1, keepdims=True), 1e-12)
    sim = np.clip(ln @ cn.T, -1.0, 1.0)
    return sim.argmax(1)


def serial_fedclust_round(batch_solver, group_params_list, local_flat,
                          X, Y, n, keys, *, d_head: int):
    """The would-be-retired FedClust round: host partial-weight cosine
    E-step, one solver launch per non-empty cluster, host rebuild of the
    per-client flattened-model matrix — the equivalence oracle for the
    fused strategy (mirrors ``fed.rounds.serial_fesem_round``)."""
    centers = np.stack([np.asarray(flatten_updates(p))
                        for p in group_params_list])
    membership = serial_fedclust_assign(centers, local_flat, d_head)
    new_list, disc, finals_by_client = rounds_lib._serial_group_update(
        batch_solver, group_params_list, membership, X, Y, n, keys,
        collect_finals=True)
    new_local = np.asarray(local_flat).copy()
    for mi, fi in finals_by_client.items():
        new_local[mi] = fi
    return new_list, membership, new_local, disc


class FedClustTrainer(FeSEMTrainer):
    """FedClust = FeSEM's persistent local-model state + partial-weight
    cosine assignment. Everything else — the pinned device matrix vs the
    population's lazy host rows, the block carry, the async stream state,
    checkpointing — is inherited unchanged."""

    framework = "fedclust"

    def _exec_spec(self) -> dict:
        return {"n_groups": self.m, "eta_g": 0.0,
                "assign_fn": make_fedclust_assign(
                    fedclust_head_dim(self.model_size,
                                      self.cfg.fedclust_frac)),
                "state_update_fn": fesem_state_update}


# ---------------------------------------------------------------------------
# LCFL: local-loss assignment with hysteresis
# ---------------------------------------------------------------------------
def make_lcfl_assign(model, margin: float):
    """Assignment stage: per-client loss under all m stacked models (like
    IFCA), but a client with a current group keeps it unless the best
    rival's loss undercuts it by more than the multiplicative ``margin``
    (``FedConfig.lcfl_margin``). state: the cohort's (K,) current group
    ids, -1 = never assigned (always takes the argmin)."""
    loss_one = client_lib.client_mean_loss(model)

    def assign(group_params, X, Y, n, state):
        per_client = jax.vmap(loss_one, in_axes=(None, 0, 0, 0))
        losses = jax.vmap(lambda gp: per_client(gp, X, Y, n))(group_params)
        m = losses.shape[0]                                  # (m, K)
        best = jnp.argmin(losses, axis=0).astype(jnp.int32)
        best_loss = jnp.min(losses, axis=0)
        cur = state.astype(jnp.int32)
        valid = (cur >= 0) & (cur < m)
        cur_c = jnp.clip(cur, 0, m - 1)
        cur_loss = jnp.take_along_axis(losses, cur_c[None, :], axis=0)[0]
        keep = valid & (cur_loss <= best_loss * (1.0 + margin))
        return jnp.where(keep, cur_c, best)

    return assign


def serial_lcfl_assign(losses, cur, margin: float) -> np.ndarray:
    """Host numpy oracle of the LCFL hysteresis rule. losses: (m, K)
    per-client losses under each group model; cur: (K,) current ids."""
    losses = np.asarray(losses)
    m = losses.shape[0]
    best = losses.argmin(0)
    best_loss = losses.min(0)
    cur = np.asarray(cur)
    valid = (cur >= 0) & (cur < m)
    cur_c = np.clip(cur, 0, m - 1)
    cur_loss = np.take_along_axis(losses, cur_c[None, :], axis=0)[0]
    keep = valid & (cur_loss <= best_loss * (1.0 + margin))
    return np.where(keep, cur_c, best).astype(np.int64)


def serial_lcfl_round(batch_solver, loss_fn, group_params_list, cur,
                      X, Y, n, keys, *, margin: float):
    """The would-be-retired LCFL round: one loss dispatch per group, the
    host hysteresis rule, one solver launch per non-empty cluster — the
    equivalence oracle for the fused strategy (mirrors
    ``fed.rounds.serial_ifca_round``)."""
    losses = np.stack([np.asarray(loss_fn(p, X, Y, n))
                       for p in group_params_list])
    membership = serial_lcfl_assign(losses, cur, margin)
    new_list, disc, _ = rounds_lib._serial_group_update(
        batch_solver, group_params_list, membership, X, Y, n, keys)
    return new_list, membership, disc


class LCFLTrainer(GroupedTrainer):
    """Loss-driven clustering with hysteresis: IFCA's m-model broadcast
    and in-program loss argmin, plus a stickiness margin read from the
    persistent membership column — the assignment state is the cohort's
    current group ids, nothing new is carried."""

    framework = "lcfl"

    def __init__(self, model, data, cfg: FedConfig, mesh=None,
                 population=None):
        super().__init__(model, data, cfg, mesh=mesh, population=population)
        keys = jax.random.split(jax.random.PRNGKey(cfg.seed + 37), self.m)
        # random cluster-center initializations, like IFCA
        self.group_params = rounds_lib.stack_trees(
            [model.init(k) for k in keys])

    def _exec_spec(self) -> dict:
        return {"n_groups": self.m, "eta_g": 0.0,
                "assign_fn": make_lcfl_assign(self.model,
                                              self.cfg.lcfl_margin)}

    def _stage_comm(self, k: int):
        # like IFCA: the client needs every group model to score it
        self.comm_params += (self.m + 1) * k * self.model_size

    def _block_kwargs(self) -> dict:
        kw = dict(self._exec_spec())
        # per-step assignment state = the carried membership's cohort rows
        # (padded lanes are redirected to the trash row, whose -1 reads as
        # "never assigned" — they aggregate with weight 0 regardless)
        kw["make_state"] = lambda aux, idx, mem: mem[idx]
        return kw

    def _async_stream_arg(self, idx):
        return jnp.asarray(self.membership[idx], jnp.int32)

    def round(self, t: int, idx=None) -> RoundMetrics:
        with self.obs.span("round", t=t):
            if idx is None:
                idx = self._select()
            self.comm_params += (self.m + 1) * len(idx) * self.model_size
            x, y, n, keys = self._stage_cohort(idx)
            out = self._round_executor()(
                self.group_params,
                jnp.asarray(self.membership[idx], jnp.int32), x, y, n, keys)
            with self.obs.span("fold"):
                self.group_params = out.group_params
                with self.obs.span("sync"):
                    mem = np.asarray(out.membership)
                self._adopt_membership(idx, mem)
                return self._fold_round(t, out, idx)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
class StrategySpec(NamedTuple):
    """One registered assignment strategy.

    state_kind names the shape of the ``assign_fn``'s state argument so
    generic harnesses (the property tests) can build one:
      "static"      no assign_fn — membership is fixed server state
      "none"        assign_fn ignores its state (IFCA)
      "membership"  (K,) int32 current group ids, -1 = cold (LCFL)
      "local_flat"  {"local_flat": (N, d_w), "idx": (K,)} (FeSEM, FedClust)
    """
    name: str
    trainer: type
    state_kind: str
    make_assign: Callable | None    # (model, d_w, cfg) -> assign_fn
    description: str


_REGISTRY: dict[str, StrategySpec] = {}


def register(spec: StrategySpec) -> StrategySpec:
    if spec.state_kind not in ("static", "none", "membership", "local_flat"):
        raise ValueError(f"unknown state_kind {spec.state_kind!r}")
    if spec.name in _REGISTRY:
        raise ValueError(f"strategy {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get_strategy(name: str) -> StrategySpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown strategy {name!r}; available: "
                       f"{available_strategies()}") from None


def available_strategies() -> list:
    return sorted(_REGISTRY)


def make_trainer(name: str, model, data, cfg: FedConfig, mesh=None,
                 population=None):
    """Construct the registered strategy's trainer (the zoo entry point)."""
    spec = get_strategy(name)
    return spec.trainer(model, data, cfg, mesh=mesh, population=population)


def _register_builtin():
    from repro.core.fedgroup import FedGroupTrainer
    from repro.fed.fesem import make_fesem_assign
    from repro.fed.ifca import IFCATrainer, make_ifca_assign

    register(StrategySpec(
        "static", FedGroupTrainer, "static", None,
        "FedGroup eq.-9 cold-start assignment, static thereafter "
        "(optionally shift-migrated via FedConfig.shift_threshold)"))
    register(StrategySpec(
        "ifca", IFCATrainer, "none",
        lambda model, d_w, cfg: make_ifca_assign(model),
        "per-round argmin mean local loss over all m models"))
    register(StrategySpec(
        "fesem", FeSEMTrainer, "local_flat",
        lambda model, d_w, cfg: make_fesem_assign(),
        "argmin-l2 E-step of local models against flattened centers"))
    register(StrategySpec(
        "fedclust", FedClustTrainer, "local_flat",
        lambda model, d_w, cfg: make_fedclust_assign(
            fedclust_head_dim(d_w, cfg.fedclust_frac)),
        "argmax partial-weight (trailing-head) cosine similarity"))
    register(StrategySpec(
        "lcfl", LCFLTrainer, "membership",
        lambda model, d_w, cfg: make_lcfl_assign(model, cfg.lcfl_margin),
        "argmin local loss with multiplicative hysteresis margin"))


_register_builtin()
