"""FedGroup / FedGrouProx — the paper's contribution (Algorithms 2 & 3).

Key pieces, mapped to the paper:
  * group cold start  (Alg. 3): pre-train α·m clients one ClientUpdate from
    w0, flatten updates into ΔW, then either
      - EDC branch:  V = truncatedSVD(ΔWᵀ, m); embed E = K(ΔW, Vᵀ);
                     K-Means++ on E                     (eq. 8)
      - MADC branch: M = K(ΔW, ΔW); MADC proximity; hierarchical complete
                     linkage                            (eq. 7)
  * client cold start (eq. 9): newcomer takes one pre-training update from
    the *auxiliary global model* and joins argmin_j normalized cosine
    dissimilarity to the group's latest update direction.
  * training round    (Alg. 2): intra-group FedAvg/FedProx, optional
    inter-group aggregation (η_G), global model = plain mean of groups.
  * ablations: RCC (random cluster centres), RAC (randomly assign cold).

Group membership is *static* once assigned (the paper's main efficiency
argument vs IFCA/FeSEM, which reschedule every round) — unless
``FedConfig.shift_threshold`` turns on the FlexCFL-style *shift detector*:
every ``shift_check_every`` rounds, each assigned cohort client with a
cached eq.-9 direction is re-probed with one pre-training pass from the
current auxiliary model, and a client whose fresh direction drifted beyond
the threshold (cosine dissimilarity ``(1 - cos)/2``) is re-routed through
eq. 9 against the current group update directions — a *migration*, counted
into the ``rounds.migrations`` metric. The stale cached direction row is
invalidated before the fresh one is cached, so any later re-cold-start
recomputes rather than reuses it.

Group state is an m-stacked pytree (leading axis = group) and every round is
ONE device dispatch through ``fed.rounds.make_round_executor`` — the serial
per-group solver loop of the seed implementation survives only as the
equivalence/benchmark oracle ``fed.rounds.serial_reference_round``.

In ``population=`` mode (``fed.population``) the trainer streams scheduled
cohorts from a host-resident ``ClientStore``; the newcomer *arrival
process* then feeds the eq.-9 client cold start round after round — the
regime the paper's cold-start mechanism is designed for — with the
pre-training directions cached in the persistent per-client state table.
Both feeding modes ride the executor's mesh placement (1-D client
parallelism, or the 2-D ``(data, model)`` mesh that additionally shards
the local solver's parameter dim — docs/scaling.md).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cluster as cluster_lib
from repro.core import measures
from repro.fed import client as client_lib
from repro.fed.engine import FedConfig, GroupedTrainer, RoundMetrics
from repro.models.modules import flatten_updates


class FedGroupTrainer(GroupedTrainer):
    framework = "fedgroup"

    def __init__(self, model, data, cfg: FedConfig, mesh=None,
                 population=None):
        super().__init__(model, data, cfg, mesh=mesh, population=population)
        # group state: pytree stacked over the group axis + (m, d_w) latest
        # flattened update direction Δw^(g)
        self.group_params = jax.tree_util.tree_map(
            lambda p: jnp.stack([p] * self.m), self.params)
        self.group_delta = None
        # 1-epoch pre-training solver for newcomer cold start (the paper:
        # pre-training does not occupy a whole round)
        self.pretrain_solver = client_lib.make_batch_solver(
            model, epochs=1, batch_size=cfg.batch_size, lr=cfg.lr, mu=0.0,
            max_samples=self._max_samples)
        self.cold_started = False
        self.last_cold = 0          # newcomers cold-started last round
        # shift detector (FedConfig.shift_threshold): pinned-mode direction
        # cache (population mode keeps rows in the ClientStateTable), the
        # check-cadence clock, and the last check's (probed, migrated)
        self._pin_dirs = None
        self._shift_tick = 0
        self._shift_last = (0, 0)
        self._last_shifted = np.empty(0, np.int64)

    def _exec_spec(self) -> dict:
        return {"n_groups": self.m, "eta_g": self.cfg.eta_g}

    # ------------------------------------------------------------------
    # Cached eq.-9 directions: one cache API over both feeding modes —
    # the persistent ClientStateTable rows when streaming, a trainer-owned
    # lazy table when pinned (materialized only when the detector needs it)
    # ------------------------------------------------------------------
    def _shift_enabled(self) -> bool:
        return self.cfg.shift_threshold is not None

    def _set_dirs(self, idx, rows):
        rows = np.asarray(rows, np.float32)
        if self.population is not None:
            self.population.state.set_pretrain_dir(idx, rows)
            return
        if self._pin_dirs is None:
            from repro.fed.store import _LazyRows
            self._pin_dirs = _LazyRows(np.zeros(rows.shape[-1], np.float32))
        self._pin_dirs.scatter(idx, rows)

    def _has_dirs(self, idx) -> np.ndarray:
        if self.population is not None:
            return self.population.state.has_pretrain_dir(idx)
        if self._pin_dirs is None:
            return np.zeros(len(np.asarray(idx)), bool)
        return self._pin_dirs.has(idx)

    def _get_dirs(self, idx) -> np.ndarray:
        if self.population is not None:
            return self.population.state.get_pretrain_dir(idx)
        return self._pin_dirs.gather(idx)

    def _invalidate_dirs(self, idx):
        if self.population is not None:
            self.population.state.invalidate_pretrain_dir(idx)
        elif self._pin_dirs is not None:
            self._pin_dirs.delete(idx)

    # ------------------------------------------------------------------
    # Group cold start (Algorithm 3)
    # ------------------------------------------------------------------
    def group_cold_start(self):
        cfg = self.cfg
        if self.population is not None:
            # pre-train from the *currently active* population only — the
            # not-yet-arrived clients are exactly the ones the eq.-9 client
            # cold start will route, round by round, as they appear
            pool = self.population.scheduler.active_ids()
        else:
            pool = self.n_clients
        pool_size = pool if isinstance(pool, int) else len(pool)
        n_pre = min(cfg.pretrain_scale * self.m, pool_size)
        pre_idx = self.rng.choice(pool, n_pre, replace=False)
        deltas, _, _ = self._solve(self.params, pre_idx)
        self.comm_params += 2 * len(pre_idx) * self.model_size
        dW = jax.vmap(flatten_updates)(deltas)                 # (n_pre, d_w)

        if cfg.rcc:                                            # ablation
            labels = self.rng.integers(0, self.m, n_pre)
            self._edc_info = None
        elif cfg.measure == "edc":
            # distinct subkeys: reusing one key for both the randomized
            # SVD's test matrix and the K-Means++ seeding correlates the
            # embedding directions with the seeding draws
            self.key, sk_svd, sk_km = jax.random.split(self.key, 3)
            E, V = measures.edc_embed(dW, self.m, key=sk_svd)
            assign, centers = cluster_lib.kmeans_pp(sk_km, E, self.m)
            labels = np.asarray(assign)
            self._edc_info = {"embedding": np.asarray(E),
                              "inertia": float(cluster_lib.kmeans_inertia(
                                  E, assign, centers))}
        elif cfg.measure == "madc":
            M = measures.cosine_similarity_matrix(dW)
            # blocked Pallas kernel above the measured crossover size,
            # reference broadcast below it (kernels.ops.madc_crossover_n)
            Mp = measures.madc(M, use_kernel=True)
            labels = cluster_lib.hierarchical(np.asarray(Mp), self.m)
            self._edc_info = None
        else:
            raise ValueError(cfg.measure)

        self._adopt_membership(pre_idx, labels)
        # segment mean over pre-trained clients: W[j, i] = 1/|G_j| for
        # members, zero rows for empty groups (they stay at w0 with Δ = 0)
        W = np.zeros((self.m, n_pre), np.float32)
        for j in range(self.m):
            members = np.where(labels == j)[0]
            if len(members):
                W[j, members] = 1.0 / len(members)
        Wj = jnp.asarray(W)
        mean_delta = jax.tree_util.tree_map(
            lambda d: (Wj @ d.reshape(n_pre, -1)).reshape(
                (self.m,) + d.shape[1:]), deltas)
        self.group_params = jax.tree_util.tree_map(
            lambda p, d: p[None] + d, self.params, mean_delta)
        # flattening the already-aggregated per-leaf means equals Wj @ dW
        # without a second pass over the (n_pre, d_w) update matrix
        self.group_delta = jax.vmap(flatten_updates)(mean_delta)  # (m, d_w)
        if self.population is not None or self._shift_enabled():
            # cache the pre-trained clients' update directions too, so the
            # Alg.-3 founders are as shift-detectable as eq.-9 newcomers
            self._set_dirs(pre_idx, np.asarray(dW))
        self.cold_started = True
        return pre_idx, labels

    # ------------------------------------------------------------------
    # Client cold start (eq. 9)
    # ------------------------------------------------------------------
    def client_cold_start(self, cold_idx: np.ndarray):
        cfg = self.cfg
        if len(cold_idx) == 0:
            return
        self.obs.registry.inc("rounds.cold_started", len(cold_idx))
        with self.obs.span("cold-start", n=int(len(cold_idx))):
            if cfg.rac:                                        # ablation
                self._adopt_membership(cold_idx,
                                       self.rng.integers(0, self.m,
                                                         len(cold_idx)))
                return
            x, y, n = self._client_batch(cold_idx)
            self.key, sk = jax.random.split(self.key)
            keys = jax.random.split(sk, len(cold_idx))
            deltas, _ = self.pretrain_solver(self.params, x, y, n, keys)
            dpre = jax.vmap(flatten_updates)(deltas)           # (c, d_w)
            if self.population is not None or self._shift_enabled():
                # cache the pre-training directions (persistent state
                # table when streaming, trainer-owned rows when pinned):
                # newcomer analytics, re-clustering and the shift
                # detector reuse them
                self._set_dirs(cold_idx, np.asarray(dpre))
            sim = measures.cosine_similarity_matrix(dpre, self.group_delta)
            dis = (-sim + 1.0) / 2.0                           # (c, m)
            self._adopt_membership(cold_idx,
                                   np.asarray(jnp.argmin(dis, axis=1)))

    # ------------------------------------------------------------------
    # Shift detection + migration (FlexCFL-style, FedConfig.shift_threshold)
    # ------------------------------------------------------------------
    def _maybe_shift(self, idx):
        """Probe the cohort's assigned, direction-cached clients for
        distribution shift and migrate the drifted ones through eq. 9.

        One pre-training pass from the current auxiliary model per probed
        client (accounted as 1 model down + 1 update up); drift is the
        normalized cosine dissimilarity ``(1 - cos)/2`` between the fresh
        and cached directions. A drifted client's stale cached row is
        *invalidated* first — a later re-cold-start must recompute, never
        reuse it — then the fresh direction is cached and the client is
        re-assigned by eq. 9 against the current group update directions
        (an ``_adopt_membership`` write, so migrations hit the registry).
        Returns the migrated client ids."""
        cfg = self.cfg
        none = np.empty(0, np.int64)
        self._last_shifted = none
        if not self._shift_enabled() or not self.cold_started \
                or self.group_delta is None:
            return none
        tick = self._shift_tick
        self._shift_tick += 1
        if tick % max(int(cfg.shift_check_every), 1) != 0:
            return none
        idx = np.asarray(idx)
        assigned = idx[self.membership[idx] >= 0]
        checked = assigned[self._has_dirs(assigned)]
        self._shift_last = (len(checked), 0)
        if len(checked) == 0:
            return none
        self.obs.registry.inc("rounds.shift_checks", len(checked))
        self.comm_params += 2 * len(checked) * self.model_size
        x, y, n = self._client_batch(checked)
        self.key, sk = jax.random.split(self.key)
        keys = jax.random.split(sk, len(checked))
        deltas, _ = self.pretrain_solver(self.params, x, y, n, keys)
        fresh = np.asarray(jax.vmap(flatten_updates)(deltas))  # (c, d_w)
        cached = self._get_dirs(checked)
        dot = np.sum(fresh * cached, axis=1)
        den = np.linalg.norm(fresh, axis=1) * np.linalg.norm(cached, axis=1)
        drift = (1.0 - dot / np.maximum(den, 1e-12)) / 2.0
        moved = drift > float(cfg.shift_threshold)
        shifted = checked[moved].astype(np.int64)
        self._shift_last = (len(checked), len(shifted))
        if len(shifted) == 0:
            return none
        self._invalidate_dirs(shifted)
        self._set_dirs(shifted, fresh[moved])
        sim = measures.cosine_similarity_matrix(
            jnp.asarray(fresh[moved]), self.group_delta)
        dis = (-sim + 1.0) / 2.0
        self._adopt_membership(shifted, np.asarray(jnp.argmin(dis, axis=1)))
        self._last_shifted = shifted
        return shifted

    # ------------------------------------------------------------------
    # Round-block staging: blocks break on host events (Alg. 3 cold start,
    # eq.-9 newcomers in a staged cohort) — membership is static otherwise
    # ------------------------------------------------------------------
    def _host_round_pre(self) -> bool:
        # shift detection is host work between every round, so an enabled
        # detector pins the trainer to the per-round path (no scan blocks)
        return not self.cold_started or self._shift_enabled()

    def _needs_host(self, idx) -> bool:
        return bool((self.membership[idx] < 0).any())

    def _carry_group_delta(self):
        # set by group_cold_start — _host_round_pre keeps blocks from
        # staging before it ran
        return self.group_delta

    def _carry_refs(self, carry: dict):
        super()._carry_refs(carry)
        self.group_delta = carry["group_delta"]

    # -- async runtime hooks: Alg. 3 before staging, eq. 9 at stage time ---
    def _async_host_pre(self):
        if not self.cold_started:
            self.group_cold_start()

    def _async_cold(self, idx) -> np.ndarray:
        # the synchronous round()'s cold segment, run at stage time: the
        # newcomers' eq.-9 routing uses the post-last-fold auxiliary
        # global model + update directions (self.params / self.group_delta
        # are re-pointed at the folded carry after every fold)
        idx = np.asarray(idx)
        # shift check precedes the cold segment, exactly as in round();
        # migrated ids ride out with the cold ids so the pinned async loop
        # patches their membership rows into the device carry
        shifted = self._maybe_shift(idx)
        cold = idx[self.membership[idx] < 0]
        self.last_cold = len(cold)
        self.comm_params += 2 * len(cold) * self.model_size
        self.client_cold_start(cold)
        return np.concatenate([shifted, cold]) if len(shifted) else cold

    def _async_stream_arg(self, idx):
        return jnp.asarray(self.membership[idx], jnp.int32)

    def _async_adopt(self, out, idx, folded_groups, folded_global):
        super()._async_adopt(out, idx, folded_groups, folded_global)
        self.group_delta = out.group_delta_flat
        self.params = folded_global

    # ------------------------------------------------------------------
    # Checkpointing: + eq.-9 update directions and the cold-start flags
    # (a resumed trainer must NOT re-run Alg. 3 — membership is static)
    # ------------------------------------------------------------------
    def _ckpt_model_tree(self) -> dict:
        tree = super()._ckpt_model_tree()
        # group_delta is None until group cold start; zeros keep the
        # checkpoint schema fixed and "has_group_delta" in the metadata
        # records which it was
        tree["group_delta"] = self.group_delta \
            if self.group_delta is not None \
            else jnp.zeros((self.m, self.model_size), jnp.float32)
        return tree

    def _ckpt_load_model(self, tree: dict):
        super()._ckpt_load_model(tree)
        self.group_delta = tree["group_delta"]

    def _ckpt_meta_extra(self) -> dict:
        return {"cold_started": bool(self.cold_started),
                "last_cold": int(self.last_cold),
                "has_group_delta": self.group_delta is not None,
                "shift_tick": int(self._shift_tick)}

    def _ckpt_apply_extra(self, extra: dict):
        self.cold_started = bool(extra["cold_started"])
        self.last_cold = int(extra["last_cold"])
        if not extra["has_group_delta"]:
            self.group_delta = None
        self._shift_tick = int(extra.get("shift_tick", 0))

    def _ckpt_state_arrays(self) -> dict:
        # pinned-mode direction cache (population rows checkpoint through
        # the state table); variable row count is fine — the load template
        # is archive-driven
        out = super()._ckpt_state_arrays()
        if self._pin_dirs is not None:
            for k, v in self._pin_dirs.ckpt_arrays().items():
                out[f"fg_dir_{k}"] = v
        return out

    def _ckpt_apply_state(self, arrays: dict):
        super()._ckpt_apply_state(arrays)
        if "fg_dir_ids" in arrays:
            from repro.fed.store import _LazyRows
            self._pin_dirs = _LazyRows.from_ckpt(
                {k: arrays[f"fg_dir_{k}"]
                 for k in ("ids", "rows", "default")})

    def _round_record(self, m) -> dict:
        rec = super()._round_record(m)
        rec["cold"] = int(self.last_cold)
        rec["eta_g"] = float(self.cfg.eta_g)
        if self._shift_enabled():
            checked, migrated = self._shift_last
            rec["shift_checked"] = int(checked)
            rec["shift_migrations"] = int(migrated)
        return rec

    # ------------------------------------------------------------------
    # Round (Algorithm 2) — one fused dispatch over all groups
    # ------------------------------------------------------------------
    def round(self, t: int, idx=None) -> RoundMetrics:
        with self.obs.span("round", t=t):
            if not self.cold_started:
                self.group_cold_start()

            if idx is None:
                idx = self._select()
            idx = np.asarray(idx)
            self._maybe_shift(idx)
            cold = idx[self.membership[idx] < 0]
            self.last_cold = len(cold)
            # cold start: 1 global model down + 1 pretrain update up per
            # newcomer
            self.comm_params += 2 * len(cold) * self.model_size
            self.client_cold_start(cold)
            # per-round: 1 group model down + 1 update up per client
            self.comm_params += 2 * len(idx) * self.model_size

            x, y, n, keys = self._stage_cohort(idx)
            out = self._round_executor()(
                self.group_params,
                jnp.asarray(self.membership[idx], jnp.int32), x, y, n, keys)
            with self.obs.span("fold"):
                self.group_params = out.group_params
                self.group_delta = out.group_delta_flat
                # auxiliary global model: unweighted average of group models
                self.params = out.global_params
                return self._fold_round(t, out, idx)


class FedGrouProxTrainer(FedGroupTrainer):
    """FedGroup + FedProx local solver (the paper's FedGrouProx)."""
    framework = "fedgrouprox"

    def __init__(self, model, data, cfg: FedConfig, mesh=None,
                 population=None):
        if cfg.mu <= 0:
            cfg = dataclasses.replace(cfg, mu=0.01)
        super().__init__(model, data, cfg, mesh=mesh, population=population)
