"""Round-based federated training engines: FedAvg / FedProx base trainer.

Two feeding modes share one compiled round program:

  * pinned (default, small N): the padded per-client train/eval stacks are
    uploaded once at init and selection is a device gather — the fast path
    and the streamed path's equivalence oracle. The train stack is packed
    client-major, so a cohort is K block copies (docs/scaling.md).
  * ``population=`` (``fed.population.Population``): the population stays
    host-resident in a ``fed.store.ClientStore`` and only the scheduled
    round cohort is streamed to device, double-buffered so the next
    cohort's H2D transfer overlaps the running round; evaluation streams
    fixed-size client blocks. Population size is then bounded by host
    memory (or disk, with memmapped shards) instead of device memory.

When more than one device is visible the round executor's client axis is
sharded over the mesh's data axes (``fed.parallel.make_sharded_executor``);
a single device gets the plain jit path, and a 2-D ``(data, model)`` mesh
(``launch.mesh.make_fed_mesh`` / ``REPRO_MODEL_AXIS``) additionally shards
the local solver's parameter dim over "model" — see docs/scaling.md.
Cohort *selection* draws from a
dedicated ``select_rng`` stream (distinct from the cold-start/ablation
``rng``), so a same-seed streamed population reproduces the pinned
trainer's selection sequence exactly.

``FedConfig.block_size > 1`` turns on *round-block execution* on the
pinned path: ``run()`` stages up to ``block_size`` upcoming cohorts (+
keys + zero-weight dropout padding) on the host — selection never depends
on device results — and dispatches them as ONE scan-fused program
(``fed.rounds.make_block_executor``) with the group state carried and
*donated* across rounds, fetching the stacked per-round metrics once per
block. Blocks break back to the per-round path on anything that needs the
host between rounds: group cold start, cold newcomers in a staged cohort,
or a streamed population (whose arrivals must be observed round by
round). ``FedConfig.eval_every`` sets the evaluation cadence on both
paths (1 = every round, the paper's tables; skipped rounds record NaN
accuracy, which ``History`` ignores).

``FedConfig.async_depth >= 1`` switches ``run()`` to the *asynchronous*
scheduler loop (``_run_async``): up to ``async_depth`` cohort dispatches
stay in flight at once and each completed dispatch is folded into the
live group state with FedAsync staleness weights α·(s+1)^(-β), where the
staleness s is counted per group (``ClientStateTable.init_group_version``
/ the pinned trainer's own clock). Every dispatch holds a *lease*: a
dispatch not ready by ``async_lease_timeout`` is abandoned and requeued
with capped exponential backoff (``async_backoff``/``async_backoff_cap``,
at most ``async_max_retries`` times), so a dead client or straggler trace
degrades throughput instead of stalling the loop. Degradation counters
(dispatches, folds, max in-flight depth, lease expiries, requeues, a
staleness histogram) surface in ``History.async_stats`` and — when
streaming — ``Population.stats``. The D=1 / weight-1.0 configuration is
the *equivalence mode*: bit-identical to the synchronous block (pinned)
and per-round (streamed) paths — tests/test_async.py holds all four
frameworks to it. See docs/architecture.md, "Async execution &
staleness".
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.federated import FederatedData
from repro.fed import client as client_lib
from repro.fed import leases as leases_lib
from repro.fed import parallel as parallel_lib
from repro.fed import rounds as rounds_lib
from repro.fed import server as server_lib
from repro.models.paper_models import ModelSpec
from repro.obs import telemetry as obs_lib

# the async runtime's lease record — shared with the coordinator/worker
# control plane (fed.leases generalizes what PR 7 built here)
_AsyncLease = leases_lib.Lease


@jax.jit
def gather_cohort(stack, sel):
    """A cohort's rows of the pinned ``ClientStack`` as ONE program, named
    ``jit_gather_cohort`` in a profile (three eager gathers would be three
    programs, each behind its own index checks): K block copies
    (``fed.rounds.gather_clients``)."""
    return rounds_lib.gather_clients(stack, sel)


@dataclass
class FedConfig:
    n_rounds: int = 50
    clients_per_round: int = 20          # K
    local_epochs: int = 20               # E
    batch_size: int = 10                 # B
    lr: float = 0.03
    mu: float = 0.0                      # FedProx proximal weight (0 = FedAvg)
    seed: int = 0
    # CFL knobs
    n_groups: int = 3                    # m
    pretrain_scale: int = 20             # alpha (pre-train alpha*m clients)
    eta_g: float = 0.0                   # inter-group aggregation lr
    measure: str = "edc"                 # edc | madc
    rcc: bool = False                    # ablation: random cluster centers
    rac: bool = False                    # ablation: randomly assign cold clients
    svd_iters: int = 4
    dropout_rate: float = 0.0            # per-round client drop probability
                                         # (network jitter, paper §3.3)
    eval_every: int = 1                  # evaluate every e-th round (1 =
                                         # every round, the paper's tables)
    block_size: int = 1                  # rounds fused per scan dispatch on
                                         # the pinned path (1 = per-round)
    # in-program update quarantine: screen non-finite / norm-outlier client
    # updates into the zero-weight path (fed.rounds) so poisoned payloads
    # never touch group params; counts surface in RoundMetrics.quarantined
    quarantine: bool = False
    quarantine_mult: float = 10.0        # outlier threshold: mult x median
                                         # cohort update norm
    # checkpoint/restore: every `checkpoint_every` completed rounds write an
    # atomic ckpt_<t>.npz into `checkpoint_dir` (0 / None = off); a fresh
    # same-config trainer resumes bit-identically via load_checkpoint()
    checkpoint_every: int = 0
    checkpoint_dir: str | None = None
    # retention: after a successful cadence write keep only the newest
    # `checkpoint_keep` ckpt_<t>.npz archives (0 = keep all); pruning is
    # atomic-after-write, so the latest checkpoint is never at risk
    checkpoint_keep: int = 0
    # asynchronous runtime (0 = synchronous): up to `async_depth` cohort
    # dispatches in flight, folded FIFO into the live group state with
    # FedAsync staleness weights alpha * (staleness + 1)^(-beta) — the
    # staleness counted per group. Depth 1 with the default alpha=1/beta=0
    # is the equivalence mode: weight 1.0 everywhere, bit-identical to the
    # synchronous paths.
    async_depth: int = 0
    async_alpha: float = 1.0
    async_beta: float = 0.0
    # cohort leases: a dispatch whose result is not ready within
    # `async_lease_timeout` seconds is abandoned and requeued with capped
    # exponential backoff; after `async_max_retries` requeues the run
    # raises (the cohort is unrecoverable, not merely slow)
    async_lease_timeout: float = 30.0
    async_max_retries: int = 3
    async_backoff: float = 0.05
    async_backoff_cap: float = 1.0
    # distribution-shift migration (FedGroup, FlexCFL-style): when set,
    # every `shift_check_every` rounds each assigned cohort client with a
    # cached eq.-9 direction is re-probed (one pre-training pass from the
    # current auxiliary model); cosine drift (1 - cos)/2 between the fresh
    # and cached directions beyond `shift_threshold` invalidates the cached
    # row and re-routes the client through eq. 9 — a migration, counted in
    # rounds.migrations. None (default) disables detection entirely and
    # preserves the static trainer's rng streams bit for bit.
    shift_threshold: float | None = None
    shift_check_every: int = 1
    # strategy-zoo knobs (fed.strategies): FedClust compares only the
    # trailing `fedclust_frac` of the flattened weights (the classifier
    # head in practice); LCFL keeps a client in its current group unless a
    # rival group's loss beats it by more than `lcfl_margin` (hysteresis)
    fedclust_frac: float = 0.25
    lcfl_margin: float = 0.1
    # telemetry (repro.obs): setting a directory enables span tracing and
    # streams per-round JSONL records + a Chrome trace + run_summary.json
    # there (docs/observability.md); None leaves the tracer a no-op
    telemetry_dir: str | None = None


@dataclass
class RoundMetrics:
    round: int
    weighted_acc: float
    mean_loss: float
    discrepancy: float
    quarantined: int = 0        # clients screened out by the update
                                # quarantine this round (0 when off)


@dataclass
class History:
    """Per-round metrics. Rounds skipped by the ``eval_every`` cadence
    record ``weighted_acc = nan``; the aggregates below ignore them (a NaN
    never satisfies ``>=``, and ``max_acc`` filters it explicitly).

    ``async_stats`` is the async runtime's degradation record (all-zero on
    synchronous runs): dispatches / folds / max_in_flight / lease_expiries
    / requeues counters plus ``staleness_hist``, a {max-staleness:
    fold-count} histogram. Inside a trainer it is a registry-backed view
    (``repro.obs.metrics``) over the ``async.*`` metrics — reads and
    writes land in the unified registry, whose snapshot rides checkpoint
    meta, so a resumed run reports totals consistent with an
    uninterrupted one."""

    rounds: list = field(default_factory=list)
    async_stats: dict = field(default_factory=dict)
    # engine hook fired on every add() — emits the per-round telemetry
    # record from whichever path (round / block / async fold) added it
    _on_add: object = field(default=None, repr=False, compare=False)

    def add(self, m: RoundMetrics):
        self.rounds.append(m)
        if self._on_add is not None:
            self._on_add(m)

    @property
    def max_acc(self) -> float:
        return max((r.weighted_acc for r in self.rounds
                    if not math.isnan(r.weighted_acc)), default=0.0)

    @property
    def total_quarantined(self) -> int:
        return sum(r.quarantined for r in self.rounds)

    def rounds_to_reach(self, target: float):
        for r in self.rounds:
            if r.weighted_acc >= target:
                return r.round
        return None


class FedAvgTrainer:
    """FedAvg (mu=0) / FedProx (mu>0) with a consensus global model."""

    framework = "fedavg"

    def __init__(self, model: ModelSpec, data: FederatedData | None,
                 cfg: FedConfig, mesh=None, population=None):
        self.model, self.cfg = model, cfg
        self.population = population
        self.rng = np.random.default_rng(cfg.seed)
        # cohort sampling draws from its own derived stream: the streamed
        # scheduler (same seed) replays the identical selection sequence,
        # and selection is decorrelated from the cold-start draws above
        from repro.fed.store import SELECT_STREAM
        self.select_rng = np.random.default_rng([cfg.seed, SELECT_STREAM])
        self.key = jax.random.PRNGKey(cfg.seed)
        if population is not None:
            self.data = data                    # optional at population scale
            self.n_clients = population.store.n_clients
            max_samples = population.store.max_train
        else:
            if data is None:
                raise ValueError("pass data= (pinned) or population=")
            self.data = data
            self.n_clients = data.n_clients
            max_samples = data.x_train.shape[1]
        self._max_samples = max_samples
        self.solver = client_lib.make_batch_solver(
            model, epochs=cfg.local_epochs, batch_size=cfg.batch_size,
            lr=cfg.lr, mu=cfg.mu, max_samples=max_samples)
        self.eval_fn = client_lib.make_eval_fn(model)
        self.params = model.init(jax.random.PRNGKey(cfg.seed + 1))
        self.history = History()
        from repro.models.modules import param_count
        self.model_size = param_count(self.params)
        self.comm_params = 0        # cumulative parameters transferred
        self._round_exec = None     # lazily-built single-dispatch round
        self._block_exec = None     # lazily-built scan-fused round block
        self._grouped_eval = None   # lazily-jitted fused grouped eval
        self._eval_zero_mem = None  # (N,) zeros for the consensus eval
        self._async_exec = None     # lazily-built async dispatch program
        self._async_fold_jit = None  # lazily-jitted staleness fold
        self.group_version = None   # (m,) per-group staleness clock (async)
        self._resumed = False       # load_checkpoint -> next run() keeps
                                    # restored Population.stats totals
        self._last_staleness = None  # last async fold's max staleness /
        self._last_weights = None    # per-group weights (round record)
        self._fold_alive = None     # alive cohort size of the fold being
                                    # recorded (rounds.empty_folds detector)
        # client axis sharded over "data" on multi-device (None = plain
        # jit); REPRO_MODEL_AXIS>1 auto-builds the 2-D (data, model) mesh
        self.mesh = parallel_lib.default_fed_mesh() if mesh is None else mesh
        if population is not None:
            population.attach(cfg, self.mesh)
            # one telemetry bundle per runtime: the population already owns
            # one (its degradation counters live there) — share it
            self.obs = population.obs
            self._train_stack = self._test_stack = None
        else:
            # pin the padded per-client stacks on device once — selection is
            # a device gather, not a fresh host->device upload every round.
            # The train stack is packed client-major, so a cohort is K
            # block copies; the test stack is read whole and stays as is
            self.obs = obs_lib.from_config(cfg)
            self._train_stack = jax.tree_util.tree_map(
                jnp.asarray, rounds_lib.pack_clients(
                    data.x_train, data.y_train, data.n_train))
            self._test_stack = tuple(jnp.asarray(a) for a in
                                     (data.x_test, data.y_test, data.n_test))
        self._bind_history(self.history)

    def _bind_history(self, h: History):
        """Attach a History to the telemetry layer: ``async_stats`` becomes
        the registry-backed view and every add() emits the round record."""
        h.async_stats = self.obs.async_view()
        h._on_add = self._emit_round
        self.history = h

    # -- telemetry (repro.obs) ---------------------------------------------
    def _emit_round(self, m: RoundMetrics):
        """History.add hook: registry counters + the streamed JSONL round
        record. Record fields are deterministic functions of training state
        (never wall time), so the stream is bit-stable across
        kill-and-resume."""
        reg = self.obs.registry
        reg.inc("rounds.completed")
        if not math.isnan(m.weighted_acc):
            reg.inc("rounds.evals")
        if m.quarantined:
            reg.inc("rounds.quarantined", m.quarantined)
            if self._fold_alive is not None \
                    and m.quarantined >= self._fold_alive:
                # every alive cohort delta was screened: the in-program
                # zero-weight fold left the group params untouched (an
                # identity passthrough, never a 0/0) — count it
                reg.inc("rounds.empty_folds")
        self._fold_alive = None
        if self.obs.recording:
            self.obs.round_record(self._round_record(m))

    def _round_record(self, m: RoundMetrics) -> dict:
        rec = {"kind": "round", "t": m.round, "acc": m.weighted_acc,
               "loss": m.mean_loss, "disc": m.discrepancy,
               "quarantined": m.quarantined}
        if self.group_version is not None:
            rec["group_version"] = [int(v) for v in self.group_version]
        if self._last_staleness is not None:
            rec["staleness"] = self._last_staleness
            rec["weights"] = self._last_weights
            self._last_staleness = self._last_weights = None
        return rec

    def _summary_extra(self) -> dict:
        return {"framework": self.framework,
                "rounds": len(self.history.rounds),
                "max_acc": self.history.max_acc,
                "comm_params": int(self.comm_params)}

    # -- single-dispatch round executor ------------------------------------
    def _exec_spec(self) -> dict:
        """Executor grouping: the consensus trainers run the shared group
        round with a single group; FedGroup overrides with m + η_G,
        IFCA/FeSEM additionally install their assignment stage."""
        return {"n_groups": 1, "eta_g": 0.0}

    def _round_executor(self):
        if self._round_exec is None:
            cfg = self.cfg
            fn = rounds_lib.make_round_executor(
                self.model, epochs=cfg.local_epochs,
                batch_size=cfg.batch_size, lr=cfg.lr, mu=cfg.mu,
                max_samples=self._max_samples, quarantine=cfg.quarantine,
                quarantine_mult=cfg.quarantine_mult, **self._exec_spec())
            self._round_exec = self.obs.wrap(
                "dispatch", parallel_lib.make_sharded_executor(fn, self.mesh),
                exec="round")
        return self._round_exec

    # -- scan-fused round blocks -------------------------------------------
    def _block_kwargs(self) -> dict:
        """make_block_executor extras: the executor grouping plus the
        framework's carry<->assignment-state adapters (FeSEM overrides)."""
        return dict(self._exec_spec())

    def _block_executor(self):
        if self._block_exec is None:
            cfg = self.cfg
            fn = rounds_lib.make_block_executor(
                self.model, epochs=cfg.local_epochs,
                batch_size=cfg.batch_size, lr=cfg.lr, mu=cfg.mu,
                max_samples=self._max_samples, quarantine=cfg.quarantine,
                quarantine_mult=cfg.quarantine_mult,
                sharded_stack=parallel_lib.shards_client_axis(
                    self.mesh, self.n_clients),
                **self._block_kwargs())
            self._block_exec = self.obs.wrap(
                "dispatch",
                parallel_lib.make_sharded_block_executor(fn, self.mesh),
                exec="block")
        return self._block_exec

    def _host_round_pre(self) -> bool:
        """True when the NEXT round must run on the per-round path for
        host work that precedes selection (FedGroup: group cold start)."""
        return False

    def _needs_host(self, idx) -> bool:
        """True when the selected cohort needs host work before the round
        (FedGroup: cold newcomers routed through eq.-9)."""
        return False

    def _stage_comm(self, k: int):
        """Per-staged-round communication accounting (k = alive clients)."""
        self.comm_params += 2 * k * self.model_size

    def _stage_round(self, t: int, idx):
        """One staged round: cohort ids padded to K, solver keys (the
        alive prefix draws ``split(sk, k)`` — exactly the per-round draw),
        the zero-weight alive mask, and the eval-cadence flag."""
        K = min(self.cfg.clients_per_round, self.n_clients)
        self.key, sk = jax.random.split(self.key)
        k = len(idx)
        keys = np.asarray(jax.random.split(sk, k))
        idx = np.asarray(idx, np.int32)
        if k < K:
            idx = np.concatenate([idx, np.full(K - k, idx[0], np.int32)])
            keys = np.concatenate(
                [keys, np.zeros((K - k,) + keys.shape[1:], keys.dtype)])
        alive = np.zeros(K, np.float32)
        alive[:k] = 1.0
        self._stage_comm(k)
        return idx, keys, alive, self._should_eval(t)

    def _stage_block(self, t0: int, max_b: int):
        """Stage up to ``max_b`` upcoming rounds (selection + keys never
        depend on device results). Stops at the first round that needs the
        host; a cohort already drawn for that round is returned as
        ``pending`` so the per-round fallback consumes it without
        re-drawing (the rng streams stay identical to a per-round run)."""
        staged, pending = [], None
        with self.obs.span("stage", t=t0):
            for b in range(max_b):
                if self._host_round_pre():
                    break
                idx = self._select()
                if self._needs_host(idx):
                    pending = idx
                    break
                staged.append(self._stage_round(t0 + b, idx))
        return staged, pending

    # carry construction/teardown — overridden down the trainer hierarchy
    def _membership_host(self):
        return np.zeros(self.n_clients, np.int64)    # consensus: one group

    def _stacked_group_params(self):
        return jax.tree_util.tree_map(lambda p: p[None], self.params)

    def _carry_group_delta(self):
        m = self._exec_spec()["n_groups"]
        return jnp.zeros((m, self.model_size), jnp.float32)

    def _carry_aux(self):
        return None

    def _carry_in(self) -> dict:
        mem = np.append(self._membership_host(), -1).astype(np.int32)
        return dict(group_params=self._stacked_group_params(),
                    global_params=self.params,
                    group_delta=self._carry_group_delta(),
                    membership=jnp.asarray(mem), aux=self._carry_aux())

    def _carry_refs(self, carry: dict):
        """Cheap per-fold reference sync: point the trainer's model-state
        attributes at the (device) carry — no host fetch. The async loop
        calls this after every fold so host work between dispatches
        (FedGroup's eq.-9 cold start, streamed eval) sees current state;
        ``_carry_out`` adds the O(N) host membership fetch on top and runs
        only at block end / checkpoint / run end."""
        self.params = carry["global_params"]

    def _carry_out(self, carry: dict):
        self._carry_refs(carry)

    def _run_block(self, t0: int, staged):
        idx = jnp.asarray(np.stack([s[0] for s in staged]))
        keys = jnp.asarray(np.stack([s[1] for s in staged]))
        alive = jnp.asarray(np.stack([s[2] for s in staged]))
        do_eval = np.asarray([s[3] for s in staged], bool)
        for s in staged:
            self._count_steps(self._solver_steps(s[0], s[2]))
            self._count_rows(len(s[0]))
        carry, ys = self._block_executor()(
            self._carry_in(), self._train_stack, self._test_stack,
            idx, keys, alive, jnp.asarray(do_eval))
        # ONE device fetch for the whole block's stacked metrics (and the
        # carried membership, which the grouped trainers read back)
        with self.obs.span("sync", t=t0):
            self._carry_out(carry)
            mean_loss, disc, correct, total, n_quar = (np.asarray(v)
                                                       for v in ys)
        for b in range(len(staged)):
            acc = (int(correct[b]) / max(int(total[b]), 1)
                   if do_eval[b] else float("nan"))
            self._fold_alive = int(staged[b][2].sum())
            self.history.add(RoundMetrics(t0 + b, acc, float(mean_loss[b]),
                                          float(disc[b]), int(n_quar[b])))

    # -- helpers -----------------------------------------------------------
    def _select(self):
        """The cohort draw (a ``select`` span; streamed, it includes the
        wait for the prefetched cohort)."""
        with self.obs.span("select"):
            if self.population is not None:
                return self.population.next_cohort().idx
            idx = self.select_rng.choice(self.n_clients,
                                         min(self.cfg.clients_per_round,
                                             self.n_clients), replace=False)
            if self.cfg.dropout_rate > 0.0:
                # stragglers drop out before completing the round (the
                # server aggregates whoever finished within the time
                # budget, Alg. 1)
                alive = (self.select_rng.random(len(idx))
                         >= self.cfg.dropout_rate)
                if not alive.any():
                    alive[self.select_rng.integers(len(idx))] = True
                idx = idx[alive]
            return idx

    def _client_batch(self, idx):
        if self.population is not None:
            # the live cohort's prefetched device arrays (or a slice of
            # them, e.g. the cold-start subset); store gather otherwise
            return self.population.device_batch(idx)
        sel = np.asarray(idx, np.int32)
        self._count_rows(len(sel))
        return gather_cohort(self._train_stack, jnp.asarray(sel))

    def _stage_cohort(self, idx):
        """The dispatches that stage a cohort for the per-round executor:
        its batch and its solver keys (a ``stage`` span). Counts the solver
        steps the round's dispatch runs."""
        with self.obs.span("stage"):
            x, y, n = self._client_batch(idx)
            self.key, sk = jax.random.split(self.key)
            keys = jax.random.split(sk, len(idx))
        self._count_steps(self._solver_steps(idx))
        return x, y, n, keys

    def _solver_steps(self, idx, alive=None) -> tuple:
        """(steps run, steps live) of one dispatched cohort, from the
        host-side client sizes, never a device read: every lane runs the
        solver's E * ceil(max_n / B) steps; an alive client's first
        E * ceil(n_i / B) of them are live, the rest masked."""
        e, b = self.cfg.local_epochs, self.cfg.batch_size
        sizes = (self.population.store if self.population is not None
                 else self.data).n_train
        n = np.maximum(np.asarray(sizes)[np.asarray(idx)].astype(np.int64),
                       1)
        live = e * ((n + b - 1) // b)
        if alive is not None:
            live = live * (np.asarray(alive) > 0)
        run = n.size * e * ((self._max_samples + b - 1) // b)
        return int(run), int(live.sum())

    def _count_steps(self, steps):
        run, live = steps
        self.obs.registry.inc("solver.steps_run", run)
        self.obs.registry.inc("solver.steps_live", live)

    def _count_rows(self, clients: int):
        """Rows a pinned cohort gather copies: every client's padded
        ``max_n`` rows, from host sizes."""
        self.obs.registry.inc("stage.rows_gathered",
                              clients * self._max_samples)

    def _fold_round(self, t: int, out, idx) -> RoundMetrics:
        """The rest of the per-round path's fold once the outputs are
        adopted: the eval, the host reads of the round's scalars (a
        ``sync`` span) and ``History.add``."""
        acc = self._round_eval(t)
        self._fold_alive = len(idx)
        with self.obs.span("sync"):
            m = RoundMetrics(t, acc, float(out.mean_loss),
                             float(out.discrepancy), int(out.n_quarantined))
        self.history.add(m)
        return m

    def _solve(self, params, idx):
        x, y, n = self._client_batch(idx)
        self.key, sk = jax.random.split(self.key)
        keys = jax.random.split(sk, len(idx))
        deltas, finals = self.solver(params, x, y, n, keys)
        return deltas, finals, n

    def _eval_correct(self, params, client_idx=None):
        """Streamed (population-mode) eval: (correct, total) accumulated
        over blocks of at most ``eval_batch`` clients — no full-population
        device allocation."""
        pop = self.population
        idx = pop.eval_ids() if client_idx is None else np.asarray(client_idx)
        if len(idx) == 0:
            return 0, 0
        correct = total = 0
        for block, x, y, n in pop.eval_batches(idx):
            c = self.eval_fn(params, x, y, n)
            with self.obs.span("sync"):
                correct += int(np.sum(np.asarray(c)))
                total += int(np.sum(np.asarray(n)))
        return correct, total

    def _should_eval(self, t: int) -> bool:
        e = self.cfg.eval_every
        return e <= 1 or (t + 1) % e == 0

    def _grouped_eval_fn(self):
        if self._grouped_eval is None:
            self._grouped_eval = jax.jit(
                client_lib.grouped_eval_correct(self.model))
        return self._grouped_eval

    def _fused_eval_acc(self, group_params, membership) -> float:
        """Pinned-path weighted accuracy as ONE dispatch regardless of m:
        integer correct/total counts from the fused grouped eval, divided
        on the host (the same division the block executor's stacked
        counts go through — bit-identical metrics)."""
        xt, yt, nt = self._test_stack
        c, tot = self._grouped_eval_fn()(group_params, membership,
                                         xt, yt, nt)
        with self.obs.span("sync"):
            c, tot = int(c), int(tot)
        return c / max(tot, 1)

    def _round_eval(self, t: int) -> float:
        """The per-round training loop's evaluation hook (NaN off-cadence).
        The pinned consensus path goes through the fused grouped eval with
        m=1 so the per-round and block-executor paths run the identical
        eval program."""
        if not self._should_eval(t):
            return float("nan")
        with self.obs.span("eval", t=t):
            if self.population is not None:
                return self.evaluate()
            if self._eval_zero_mem is None:
                self._eval_zero_mem = jnp.zeros(self.n_clients, jnp.int32)
            return self._fused_eval_acc(
                jax.tree_util.tree_map(lambda p: p[None], self.params),
                self._eval_zero_mem)

    def evaluate(self, params=None, client_idx=None) -> float:
        params = self.params if params is None else params
        if self.population is not None:
            correct, total = self._eval_correct(params, client_idx)
            return correct / max(total, 1)
        d = self.data
        xt, yt, nt = self._test_stack
        if client_idx is None:
            idx = np.arange(d.n_clients)
        else:
            idx = np.asarray(client_idx)
            if len(idx) == 0:
                return 0.0
            sel = jnp.asarray(idx.astype(np.int32))
            xt, yt, nt = xt[sel], yt[sel], nt[sel]
        correct = self.eval_fn(params, xt, yt, nt)
        total = d.n_test[idx].sum()
        return float(np.sum(np.asarray(correct)) / max(total, 1))

    # -- main loop ---------------------------------------------------------
    def round(self, t: int, idx=None) -> RoundMetrics:
        with self.obs.span("round", t=t):
            if idx is None:
                idx = self._select()
            x, y, n, keys = self._stage_cohort(idx)
            # downlink: 1 model per client; uplink: 1 update per client
            self.comm_params += 2 * len(idx) * self.model_size
            out = self._round_executor()(
                jax.tree_util.tree_map(lambda p: p[None], self.params),
                jnp.zeros(len(idx), jnp.int32), x, y, n, keys)
            with self.obs.span("fold"):
                self.params = out.global_params
                return self._fold_round(t, out, idx)

    def run(self, n_rounds=None) -> History:
        """The block-scheduling loop. With ``block_size > 1`` on the pinned
        path, upcoming rounds are staged on the host and dispatched as one
        scan-fused block; anything that needs the host between rounds —
        group cold start, cold newcomers in a staged cohort, a streamed
        population — breaks back to the per-round path (a cohort already
        drawn for the breaking round is carried over as ``pending``, so
        the rng streams match a pure per-round run exactly).

        Runs ``n_rounds`` MORE rounds, labelled from the current history
        length — so repeated calls keep training forward, and a trainer
        restored via ``load_checkpoint`` continues with the absolute round
        labels (and eval/checkpoint cadence) of the uninterrupted run.
        With ``checkpoint_every``/``checkpoint_dir`` set, an atomic
        snapshot lands every time a multiple of ``checkpoint_every``
        completed rounds is crossed."""
        if self.population is not None:
            if self._resumed:
                self._resumed = False    # keep the restored stats totals
            else:
                self.population.reset_stats()
        t0 = len(self.history.rounds)
        total = t0 + (n_rounds or self.cfg.n_rounds)
        if self.cfg.async_depth >= 1:
            h = self._run_async(t0, total)
            self.obs.finalize(self._summary_extra())
            return h
        blocks = self.cfg.block_size > 1 and (
            self.population is None or
            getattr(self.population, "block_stageable", False))
        t, pending = t0, None
        while t < total:
            prev = t
            if pending is not None:
                self.round(t, idx=pending)
                pending = None
                t += 1
            elif not blocks or total - t < 2:
                self.round(t)
                t += 1
            else:
                staged, pending = self._stage_block(
                    t, min(self.cfg.block_size, total - t))
                if staged:
                    self._run_block(t, staged)
                    t += len(staged)
                elif pending is None:
                    self.round(t)
                    t += 1
            self._maybe_checkpoint(prev, t)
        self.obs.finalize(self._summary_extra())
        return self.history

    # -- asynchronous runtime (FedConfig.async_depth >= 1) -------------------
    def _group_version(self):
        """The (m,) int64 per-group staleness clock: version[g] increments
        every time a fold lands clients in group g, and a dispatch's
        staleness is the clock gap between its dispatch and its fold.
        Shared by reference with the population's state table when
        streaming (like membership), trainer-owned when pinned."""
        if self.group_version is None:
            m = self._exec_spec()["n_groups"]
            if self.population is not None:
                self.group_version = \
                    self.population.state.init_group_version(m)
            else:
                self.group_version = np.zeros(m, np.int64)
        return self.group_version

    def _async_executor(self):
        """Pinned-path async dispatch program: exactly one block-executor
        scan step (same round core, same in-program gather and trash-row
        scatter, no in-program eval — the loop evaluates at fold time),
        compiled WITHOUT carry donation: the snapshot carry is shared with
        the live state and every other in-flight dispatch."""
        if self._async_exec is None:
            cfg = self.cfg
            fn = rounds_lib.make_async_dispatch_executor(
                self.model, epochs=cfg.local_epochs,
                batch_size=cfg.batch_size, lr=cfg.lr, mu=cfg.mu,
                max_samples=self._max_samples, quarantine=cfg.quarantine,
                quarantine_mult=cfg.quarantine_mult,
                sharded_stack=parallel_lib.shards_client_axis(
                    self.mesh, self.n_clients),
                **self._block_kwargs())
            self._async_exec = self.obs.wrap(
                "dispatch",
                parallel_lib.make_async_dispatch_executor(fn, self.mesh),
                exec="async")
        return self._async_exec

    def _async_fold(self):
        """The staleness fold, jitted with the current state and the
        dispatch result both donated (``fed.parallel.make_async_fold``):
        the full-carry fold when pinned, the group-params-only fold when
        streamed (membership and FeSEM rows stay host-resident there)."""
        if self._async_fold_jit is None:
            fold = (rounds_lib.make_staleness_fold()
                    if self.population is None
                    else rounds_lib.make_param_fold())
            self._async_fold_jit = parallel_lib.make_async_fold(fold)
        return self._async_fold_jit

    def _async_host_pre(self):
        """Host work that must precede async staging (FedGroup: the Alg. 3
        group cold start before the first cohort is drawn)."""

    def _async_cold(self, idx) -> np.ndarray:
        """Stage-time cold-newcomer hook; returns the cold client ids so
        the pinned loop can patch their rows into the device carry
        (FedGroup overrides with the eq.-9 client cold start)."""
        return np.empty(0, np.int64)

    def _async_stream_arg(self, idx):
        """The streamed round executor's assignment argument, built exactly
        as the trainer's synchronous ``round()`` builds it."""
        return jnp.zeros(len(idx), jnp.int32)

    def _async_adopt(self, out, idx, folded_groups, folded_global):
        """Adopt a folded *streamed* dispatch — mirrors each trainer's
        synchronous ``round()`` adoption, so the weight-1.0 fold (a
        bitwise passthrough of the dispatch result) reproduces it
        exactly."""
        self.params = folded_global

    def _stage_async(self, t: int):
        """Stage one cohort for async dispatch: host-pre hook, selection,
        cold-newcomer handling, solver keys and communication accounting —
        the same host sequence (and the same rng draw order) as the
        synchronous paths. Returns ``(cold_ids, staged_inputs)``; the
        staged inputs are kept device-resident so an expired lease can
        re-dispatch them against the then-current state, and end with the
        cohort's (steps run, steps live), counted at every dispatch."""
        with self.obs.span("stage", t=t):
            self._async_host_pre()
            idx = self._select()
            cold = np.asarray(self._async_cold(idx))
            if self.population is None:
                idx_p, keys, alive, _ = self._stage_round(t, idx)
                return cold, (jnp.asarray(idx_p), jnp.asarray(keys),
                              jnp.asarray(alive),
                              self._solver_steps(idx_p, alive))
            x, y, n = self._client_batch(idx)
            self.key, sk = jax.random.split(self.key)
            keys = jax.random.split(sk, len(idx))
            self._stage_comm(len(idx))
            return cold, (np.asarray(idx), x, y, n, keys,
                          self._async_stream_arg(idx),
                          self._solver_steps(idx))

    def _lease_ready(self, leaves) -> bool:
        """True when every device buffer of a lease's result is computed
        (tests monkeypatch this to script lease expiries)."""
        return all(l.is_ready() for l in leaves)

    def _wait_ready(self, lease: _AsyncLease) -> bool:
        """Poll a lease's result until ready or the deadline passes, the
        poll interval backing off exponentially. Readiness is checked
        before the deadline, so an already-computed result is never
        expired."""
        leaves = [l for l in jax.tree_util.tree_leaves(
            (lease.result, lease.metrics)) if hasattr(l, "is_ready")]
        pause = 1e-4
        while True:
            if self._lease_ready(leaves):
                return True
            if time.monotonic() >= lease.deadline:
                return False
            time.sleep(pause)
            pause = min(pause * 2.0, 0.005)

    def _run_async(self, t0: int, total: int) -> History:
        """The asynchronous scheduler loop: keep up to ``async_depth``
        cohort dispatches in flight against the live state, fold completed
        dispatches FIFO with per-group staleness weights, requeue expired
        leases with capped exponential backoff.

        Fold order defines the round index — a requeued cohort folds later
        and becomes a later round, exactly as an asynchronous server
        accounts a late client — and the eval / checkpoint cadence is
        evaluated at fold time. A checkpoint cadence crossing first drains
        the in-flight window to quiescence: the snapshot then carries no
        outstanding leases (the staleness clocks, counters and rng streams
        capture everything else), and a killed-and-resumed run re-stages
        bit-identically what the uninterrupted run staged after its own
        drain. Folds are FIFO rather than completion-order: on a device
        stream dispatches execute in enqueue order anyway, so FIFO loses
        no overlap and keeps the fold sequence deterministic."""
        cfg = self.cfg
        pop = self.population
        pinned = pop is None
        depth = max(1, int(cfg.async_depth))
        ver = self._group_version()
        # registry-backed view (repro.obs.metrics): the async.* schema
        # pre-seeds every counter, and the histogram dict is live — the
        # in-place bucket bumps below land in the registry
        st = self.history.async_stats
        shist = st["staleness_hist"]
        self._async_host_pre()
        carry = self._carry_in() if pinned else None
        exec_ = self._async_executor() if pinned else self._round_executor()
        fold = self._async_fold()
        policy = leases_lib.RetryPolicy(
            cfg.async_lease_timeout, cfg.async_max_retries,
            cfg.async_backoff, cfg.async_backoff_cap)
        pending = []                 # in-flight leases, FIFO fold order
        requeued = leases_lib.RequeueBuffer()  # expired, backing off
        t_stage = t0                 # cohorts staged so far
        t_fold = t0                  # rounds folded so far

        def dispatch(staged, attempts):
            self._count_steps(staged[-1])
            if pinned:
                idx_d, keys_d, alive_d, _ = staged
                self._count_rows(len(idx_d))
                result, mets = exec_(carry, self._train_stack,
                                     idx_d, keys_d, alive_d)
            else:
                result = exec_(self._stacked_group_params(), staged[5],
                               staged[1], staged[2], staged[3], staged[4])
                mets = None
            pending.append(_AsyncLease(
                staged, ver.copy(), result, mets,
                time.monotonic() + cfg.async_lease_timeout, attempts))
            st["dispatches"] += 1
            st["max_in_flight"] = max(st["max_in_flight"], len(pending))

        def fill(fresh):
            nonlocal t_stage, carry
            while len(pending) < depth:
                now = time.monotonic()
                ready = requeued.pop_ready(now)
                if ready is not None:
                    staged, attempts = ready
                    dispatch(staged, attempts)
                elif fresh and t_stage < total:
                    cold, staged = self._stage_async(t_stage)
                    if pinned and len(cold):
                        # the eq.-9 assignments happened on the host —
                        # patch the newcomers' rows into the device carry
                        # (a new membership array; in-flight dispatches
                        # keep the snapshot they were enqueued against)
                        carry = dict(
                            carry,
                            membership=carry["membership"]
                            .at[jnp.asarray(cold, jnp.int32)].set(
                                jnp.asarray(self.membership[cold],
                                            jnp.int32)))
                    dispatch(staged, 0)
                    t_stage += 1
                elif requeued and not pending:
                    # nothing in flight and every lease is backing off:
                    # sleep to the earliest retry instead of spinning
                    time.sleep(max(0.0, requeued.earliest()
                                   - time.monotonic()))
                else:
                    break

        def fold_one(lease):
            nonlocal carry, t_fold
            t = t_fold
            with self.obs.span("fold", t=t):
                s = (ver - lease.version).astype(np.int64)
                w = rounds_lib.staleness_weight(
                    s, alpha=cfg.async_alpha, beta=cfg.async_beta)
                key = str(int(s.max()) if s.size else 0)
                shist[key] = shist.get(key, 0) + 1
                if self.obs.recording:
                    self._last_staleness = int(s.max()) if s.size else 0
                    self._last_weights = [float(v)
                                          for v in np.asarray(w).ravel()]
                if pinned:
                    idx_d, _, alive_d, _ = lease.staged
                    carry = fold(carry, lease.result, idx_d, alive_d,
                                 jnp.asarray(w))
                    self._carry_refs(carry)
                    with self.obs.span("sync"):
                        mean_loss, disc, n_quar, mem = (
                            np.asarray(v) for v in lease.metrics)
                    alive_h = np.asarray(alive_d)
                    self._fold_alive = int(alive_h.sum())
                    occupied = np.unique(mem[alive_h > 0])
                    if self._should_eval(t):
                        with self.obs.span("eval", t=t):
                            acc = self._fused_eval_acc(
                                carry["group_params"],
                                carry["membership"][:-1])
                    else:
                        acc = float("nan")
                else:
                    out = lease.result
                    groups, glob = fold(self._stacked_group_params(),
                                        out.group_params, out.global_params,
                                        jnp.asarray(w))
                    self._async_adopt(out, lease.staged[0], groups, glob)
                    self._fold_alive = int(len(lease.staged[0]))
                    with self.obs.span("sync"):
                        occupied = np.unique(np.asarray(out.membership))
                    mean_loss, disc, n_quar = (out.mean_loss,
                                               out.discrepancy,
                                               out.n_quarantined)
                    acc = self._round_eval(t)
                ver[occupied] += 1
                st["folds"] += 1
                with self.obs.span("sync"):
                    m = RoundMetrics(t, acc, float(mean_loss), float(disc),
                                     int(n_quar))
                self.history.add(m)
            t_fold += 1

        def harvest():
            """Fold the FIFO head if it completes within its lease,
            abandon + requeue it with capped backoff otherwise."""
            lease = pending.pop(0)
            if self._wait_ready(lease):
                fold_one(lease)
                return True
            st["lease_expiries"] += 1
            if pop is not None:
                pop.stats["lease_expiries"] += 1
            requeued.push(lease, policy, time.monotonic())
            st["requeues"] += 1
            if pop is not None:
                pop.stats["requeues"] += 1
            return False

        while t_fold < total:
            fill(fresh=True)
            prev = t_fold
            if pending and harvest():
                e = cfg.checkpoint_every
                if e > 0 and cfg.checkpoint_dir and t_fold // e > prev // e:
                    # drain to quiescence before snapshotting — a
                    # checkpoint never carries an outstanding lease
                    while pending or requeued:
                        fill(fresh=False)
                        if pending:
                            harvest()
                    if pinned:
                        self._carry_out(carry)
                    self.save_checkpoint()
        if pinned:
            self._carry_out(carry)
        if pop is not None:
            pop.stats["writer_retries"] = pop._writer.retries
        return self.history

    # -- checkpoint/restore ------------------------------------------------
    def _maybe_checkpoint(self, prev_t: int, t: int):
        e = self.cfg.checkpoint_every
        if e > 0 and self.cfg.checkpoint_dir and t // e > prev_t // e:
            self.save_checkpoint()

    def _ckpt_model_tree(self) -> dict:
        """The device/model state a checkpoint must capture. Doubles as the
        ``load_pytree`` template: a fresh same-config trainer's live arrays
        have exactly the checkpointed shapes/dtypes."""
        return {"params": self.params, "key": self.key}

    def _ckpt_load_model(self, tree: dict):
        self.params = tree["params"]
        self.key = tree["key"]

    def _ckpt_meta_extra(self) -> dict:
        """Framework-specific JSON-able scalars (FedGroup: cold-start
        flags)."""
        return {}

    def _ckpt_apply_extra(self, extra: dict):
        pass

    def _ckpt_state_arrays(self) -> dict:
        """Framework-owned host arrays of *save-time* shape, merged into
        the checkpoint's ``state`` sub-tree next to the population tables
        (FedGroup: the pinned-mode eq.-9 direction cache). Keys must not
        collide with ``Population.ckpt_state``'s; the load template is
        archive-driven, so variable row counts are fine."""
        return {}

    def _ckpt_apply_state(self, arrays: dict):
        """Restore hook for ``_ckpt_state_arrays`` (receives the full
        ``state`` sub-tree; pick out the framework's own keys)."""
        pass

    def save_checkpoint(self, path: str | None = None) -> str:
        """Atomic full-state snapshot after ``len(history.rounds)``
        completed rounds: model/group state + both rng streams + metrics +
        comm accounting, and (when streaming) the population's scheduler
        stream and state table. ``load_checkpoint`` on a fresh same-config
        trainer resumes bit-identically."""
        from repro.checkpoint import io as ckpt_io
        t = len(self.history.rounds)
        if path is None:
            if not self.cfg.checkpoint_dir:
                raise ValueError("pass a path or set FedConfig"
                                 ".checkpoint_dir")
            path = ckpt_io.checkpoint_path(self.cfg.checkpoint_dir, t)
        # counted before the snapshot so the checkpoint's own registry
        # capture includes itself — resumed totals match uninterrupted ones
        self.obs.registry.inc("rounds.checkpoints")
        with self.obs.span("checkpoint", t=t):
            state, pop_meta = {}, None
            if self.population is not None:
                # drains the writer and syncs writer_retries into the
                # registry BEFORE the snapshot below — every degradation
                # counter reaches the checkpoint through one surface
                state, pop_meta = self.population.ckpt_state()
            state = dict(state, **self._ckpt_state_arrays())
            meta = {"framework": self.framework, "t": t,
                    "n_clients": int(self.n_clients),
                    "rng": self.rng.bit_generator.state,
                    "select_rng": self.select_rng.bit_generator.state,
                    "comm_params": int(self.comm_params),
                    "history": [[r.round, r.weighted_acc, r.mean_loss,
                                 r.discrepancy, r.quarantined]
                                for r in self.history.rounds],
                    "extra": self._ckpt_meta_extra(),
                    # async runtime state: the per-group staleness clocks
                    # (leases themselves never reach a checkpoint — the
                    # async loop drains to quiescence first)
                    "group_version": ([int(v) for v in self.group_version]
                                      if self.group_version is not None
                                      else None),
                    # the unified registry snapshot: async.* degradation
                    # counters, pop.* robustness counters, rounds.* series
                    # — one consistent mid-run capture (format v3)
                    "obs": self.obs.registry.snapshot(),
                    # fleet metadata (ckpt format v4): the coordinator's
                    # control-plane snapshot when a launch.Coordinator owns
                    # this trainer, None on single-process runs
                    "fleet": self._fleet_meta(),
                    "population": pop_meta}
            ckpt_io.save_pytree(path, {"model": self._ckpt_model_tree(),
                                       "state": state}, meta)
        if self.cfg.checkpoint_keep > 0 and self.cfg.checkpoint_dir:
            # retention AFTER the successful atomic write: the archive just
            # written is the newest, so it always survives the prune
            ckpt_io.prune_checkpoints(self.cfg.checkpoint_dir,
                                      self.cfg.checkpoint_keep)
        return path

    def _fleet_meta(self):
        """Checkpoint meta hook: the owning coordinator's control-plane
        snapshot (``launch.coordinator`` overrides this on its trainer);
        None on single-process runs."""
        return None

    def load_checkpoint(self, path_or_dir: str) -> int:
        """Restore a ``save_checkpoint`` snapshot into this trainer (fresh,
        same config, same population construction). Accepts a checkpoint
        file or a directory (picks the latest ``ckpt_*.npz`` — the
        kill-and-resume entry point). Returns the completed-round count;
        ``run(n)`` then continues exactly where the killed run left off."""
        from repro.checkpoint import io as ckpt_io
        path = path_or_dir
        if os.path.isdir(path):
            path = ckpt_io.latest_checkpoint(path)
            if path is None:
                raise FileNotFoundError(
                    f"no ckpt_*.npz checkpoints in {path_or_dir}")
        if self.history.rounds:
            raise RuntimeError("load_checkpoint needs a fresh trainer — "
                               "this one has already trained")
        meta = ckpt_io.load_metadata(path)
        if meta["framework"] != self.framework:
            raise ValueError(
                f"checkpoint was written by framework "
                f"{meta['framework']!r}, this trainer is {self.framework!r}")
        if int(meta["n_clients"]) != self.n_clients:
            raise ValueError(
                f"checkpoint population has {meta['n_clients']} clients, "
                f"this trainer has {self.n_clients}")
        if meta["population"] is not None and self.population is None:
            raise ValueError("checkpoint came from a streamed-population "
                             "run — construct the trainer with the same "
                             "population")
        # the model sub-tree's template is the live (fresh) trainer state;
        # the population sub-tree's row counts are only known at save time,
        # so its template comes from the archive's own specs
        state_tmpl = {
            k[len("state/"):]: np.zeros(shape, dtype)
            for k, (shape, dtype) in ckpt_io.saved_array_specs(path).items()
            if k.startswith("state/")}
        tree = ckpt_io.load_pytree(
            path, {"model": self._ckpt_model_tree(), "state": state_tmpl})
        self._ckpt_load_model(tree["model"])
        self._ckpt_apply_extra(meta.get("extra") or {})
        self.rng.bit_generator.state = meta["rng"]
        self.select_rng.bit_generator.state = meta["select_rng"]
        self.comm_params = int(meta["comm_params"])
        self._bind_history(History(
            [RoundMetrics(int(r[0]), float(r[1]), float(r[2]), float(r[3]),
                          int(r[4])) for r in meta["history"]]))
        gv = meta.get("group_version")
        if gv is not None:
            self._group_version()[:] = np.asarray(gv, np.int64)
        if self.population is not None:
            if meta["population"] is None:
                raise ValueError("checkpoint came from a pinned run — "
                                 "construct the trainer without population")
            self.population.ckpt_restore(
                {k: np.asarray(v) for k, v in tree["state"].items()},
                meta["population"])
        self._ckpt_apply_state(
            {k: np.asarray(v) for k, v in tree["state"].items()})
        # cumulative counters come back through the unified registry
        # snapshot (format v3); pre-v3 archives carried only async_stats
        obs_snap = meta.get("obs")
        if obs_snap is None and meta.get("async_stats"):
            obs_snap = {f"async.{k}": v
                        for k, v in meta["async_stats"].items()}
        self.obs.registry.restore(obs_snap or {})
        # drop streamed round records at/after the resume point — the
        # resumed run re-emits them, so the JSONL stream stays free of
        # duplicates and byte-identical to an uninterrupted run's
        self.obs.resume_at(int(meta["t"]))
        self._resumed = True
        return int(meta["t"])

    def close(self):
        """Stop the population prefetch thread (no-op in pinned mode) and
        finalize the telemetry artifacts (trace.json / run_summary.json)."""
        if self.population is not None:
            self.population.close()
        self.obs.finalize(self._summary_extra())


class FedProxTrainer(FedAvgTrainer):
    framework = "fedprox"

    def __init__(self, model, data, cfg: FedConfig, mesh=None,
                 population=None):
        if cfg.mu <= 0:
            cfg = dataclasses.replace(cfg, mu=0.01)
        super().__init__(model, data, cfg, mesh=mesh, population=population)


class GroupedTrainer(FedAvgTrainer):
    """Shared machinery for the clustered trainers (FedGroup, IFCA, FeSEM):
    m group models kept as an m-stacked pytree, per-client membership
    bookkeeping, and group-wise weighted-accuracy evaluation."""

    def __init__(self, model, data, cfg: FedConfig, mesh=None,
                 population=None):
        super().__init__(model, data, cfg, mesh=mesh, population=population)
        self.m = cfg.n_groups
        self._mig_last = None       # cohort membership flips last round
        if population is not None:
            # membership IS the persistent state table's column, so the
            # trainers' in-place writes survive across cohorts/restarts
            self.membership = population.state.membership
        else:
            self.membership = np.full(self.n_clients, -1, np.int64)

    def _adopt_membership(self, idx, new):
        """Write a cohort's new group assignments into the membership
        column, counting migrations (previously-assigned clients switching
        groups — FlexCFL's core drift signal) into the registry."""
        new = np.asarray(new)
        old = self.membership[idx]
        mig = int(np.sum((old >= 0) & (old != new)))
        self._mig_last = mig
        if mig:
            self.obs.registry.inc("rounds.migrations", mig)
        with self.obs.span("state-write", rows=int(len(new))):
            self.membership[idx] = new

    def _round_record(self, m: RoundMetrics) -> dict:
        rec = super()._round_record(m)
        mem = self.membership
        sizes = np.bincount(mem[mem >= 0].astype(np.int64), minlength=self.m)
        rec["group_sizes"] = [int(v) for v in sizes[:self.m]]
        if self._mig_last is not None:
            rec["migrations"] = self._mig_last
            self._mig_last = None
        return rec

    def group_param(self, j: int):
        """The j-th group's parameter pytree (view into the stacked state)."""
        return server_lib.tree_index(self.group_params, j)

    def evaluate_groups(self) -> float:
        """Weighted accuracy: each group model on the test data of all
        clients historically assigned to it (paper §5.1 metric). On the
        pinned path this is ONE fused dispatch regardless of m
        (``fed.client.grouped_eval_correct``); the streamed population
        keeps the per-group blocked eval loop (it cannot pin the test
        stacks)."""
        if self.population is not None:
            eval_ids = self.population.eval_ids()
            mem = self.membership[eval_ids]
            total_correct, total_n = 0, 0
            for j in range(self.m):
                members = eval_ids[mem == j]
                if len(members) == 0:
                    continue
                c, tot = self._eval_correct(self.group_param(j), members)
                total_correct += c
                total_n += tot
            return total_correct / max(total_n, 1)
        return self._fused_eval_acc(
            self.group_params, jnp.asarray(self.membership.astype(np.int32)))

    def _round_eval(self, t: int) -> float:
        if not self._should_eval(t):
            return float("nan")
        with self.obs.span("eval", t=t):
            return self.evaluate_groups()

    # -- round-block carry: m-stacked groups + membership ------------------
    def _membership_host(self):
        return self.membership

    def _stacked_group_params(self):
        return self.group_params

    def _carry_refs(self, carry: dict):
        super()._carry_refs(carry)
        self.group_params = carry["group_params"]

    def _carry_out(self, carry: dict):
        self._carry_refs(carry)
        self.membership[:] = np.asarray(
            carry["membership"])[:-1].astype(self.membership.dtype)

    def _async_adopt(self, out, idx, folded_groups, folded_global):
        # the grouped (IFCA-shaped) adoption: group models + the cohort's
        # membership writes; the consensus params stay untouched, exactly
        # as the synchronous round() leaves them
        self.group_params = folded_groups
        self._adopt_membership(idx, out.membership)

    # -- checkpointing: m-stacked groups + membership ----------------------
    def _ckpt_model_tree(self) -> dict:
        tree = super()._ckpt_model_tree()
        tree["group_params"] = self.group_params
        tree["membership"] = np.asarray(self.membership)
        return tree

    def _ckpt_load_model(self, tree: dict):
        super()._ckpt_load_model(tree)
        self.group_params = tree["group_params"]
        # in place: population mode shares this array with the state table
        self.membership[:] = np.asarray(
            tree["membership"]).astype(self.membership.dtype)
