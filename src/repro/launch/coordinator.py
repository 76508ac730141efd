"""The elastic coordinator: process-level fault domains for the federated
runtime.

``Coordinator`` wraps an ordinarily-constructed trainer and routes its
compiled train dispatches (``_round_executor`` / ``_block_executor`` /
``_async_executor``) through a worker fleet, while keeping everything
stateful exactly where the paper's reliable server owns it — the m-stacked
group params, the ``ClientStateTable``, membership, both rng streams, the
eq.-9 cold start, evaluation, staleness folds and checkpointing all stay on
the coordinator. Workers are stateless executors (``launch.worker``); a
job is a pure function of its message, so any worker — or the same worker
after a restart — produces the bit-identical result.

Every dispatch holds a **lease** (``fed.leases`` — the same
timeout/requeue/backoff machinery the async runtime uses in-device):
the job is sent to a worker, and if the result is not back before the
deadline — or the holder is declared dead by the heartbeat miss-threshold
detector, or chaos dropped the message — the lease is requeued with capped
exponential backoff and re-dispatched to the next live worker. After
``max_retries`` requeues the job is unrecoverable and the run raises.

Failure detection is heartbeat-driven: workers beat every
``heartbeat_interval`` seconds; a worker silent for ``heartbeat_interval *
heartbeat_miss`` seconds is declared dead (``fleet.worker_deaths``), its
leases requeue, and the fleet degrades gracefully down to a single worker.
A late heartbeat resurrects (``fleet.joins``). Elastic membership is
scripted or programmatic: ``FleetConfig.joins``/``leaves`` adopt newcomer
workers or retire live ones at a given dispatch clock, and
:meth:`Coordinator.spawn`/:meth:`Coordinator.retire` do the same on
demand. A process-mode newcomer cold-starts itself by building its trainer
replica from the ``WorkerSpec`` before joining.

Chaos injection extends the PR-6 ``FaultConfig``: ``FaultSpec``'s fleet
fields (``worker_kill``, ``heartbeat_delay``, ``msg_drop``, ``msg_dup``,
``msg_reorder``) are read per dispatch-clock tick and applied to that
dispatch's lease — a SIGKILL mid-dispatch, a muted heartbeat window, or
delivery-order faults on the transport. Because jobs are pure, every
recovery path re-converges on the bit-identical run.

Fleet-size-1 in-process mode is the equivalence anchor: arguments pass by
reference to a thread executing the trainer's own compiled closures, so
``Coordinator(trainer).run()`` is bit-identical to ``trainer.run()`` for
all four frameworks, pinned and streamed (tests/test_fleet.py) — the
entire PR-6/7/9 equivalence matrix carries over to the control plane.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

import jax

from repro.fed import leases as leases_lib
from repro.launch import transport as transport_lib
from repro.launch import worker as worker_lib
from repro.launch.transport import (ChaosRouter, HeartbeatMonitor,
                                    InProcTransport, Message, ProcTransport)
from repro.obs import metrics as metrics_lib

_MISSING = object()


@dataclass
class FleetConfig:
    """Control-plane knobs.

    transport           "inproc" (thread workers, bit-identity mode) or
                        "proc" (spawned processes, real fault domains —
                        requires ``worker_spec``; per-round pinned path
                        only; refused on a TPU backend, where the chip
                        belongs to the coordinator's process).
    heartbeat_interval  worker beat period (seconds).
    heartbeat_miss      beats missed before a worker is declared dead.
    lease_timeout /     the fleet job lease's ``fed.leases.RetryPolicy``:
    max_retries /       a job not answered by the deadline requeues with
    backoff /           capped exponential backoff, at most ``max_retries``
    backoff_cap         times.
    join_timeout        how long to wait for a live worker before the run
                        fails (covers a process worker's replica build).
    faults              scripted chaos: ``FaultConfig`` whose ``rounds``
                        map *dispatch-clock* ticks to ``FaultSpec``s; only
                        the fleet fields are read here.
    joins / leaves      elastic membership scripts: {dispatch-clock:
                        [worker names]} adopted / retired at that tick.
    worker_spec         process-mode trainer replica recipe
                        (``launch.worker.WorkerSpec``).
    """
    n_workers: int = 1
    transport: str = "inproc"
    heartbeat_interval: float = 0.05
    heartbeat_miss: int = 3
    lease_timeout: float = 60.0
    max_retries: int = 3
    backoff: float = 0.01
    backoff_cap: float = 0.25
    join_timeout: float = 180.0
    faults: object | None = None
    joins: dict | None = None
    leaves: dict | None = None
    worker_spec: worker_lib.WorkerSpec | None = None


class Coordinator:
    """Owns the trainer (and with it all training state); routes its train
    dispatches through the worker fleet. See the module docstring."""

    def __init__(self, trainer, fleet: FleetConfig | None = None):
        self.trainer = trainer
        self.fleet = fleet or FleetConfig()
        self.obs = trainer.obs
        self.obs.registry.declare(metrics_lib.FLEET_SCHEMA)
        self._policy = leases_lib.RetryPolicy(
            self.fleet.lease_timeout, self.fleet.max_retries,
            self.fleet.backoff, self.fleet.backoff_cap)
        self._monitor = HeartbeatMonitor(self.fleet.heartbeat_interval,
                                         self.fleet.heartbeat_miss)
        self._chaos = ChaosRouter(self.obs.registry)
        self._clock = 0              # train dispatches submitted (the
        self._job_id = 0             # chaos/elasticity script clock)
        self._rr = 0                 # round-robin cursor
        self._live: list = []        # adopted worker names, join order
        self._workers: dict = {}     # name -> InProcWorker (inproc mode)
        self._results: dict = {}     # job_id -> payload (delivered)
        self._done: set = set()      # completed/abandoned job ids (so a
        #                              late or duplicated result is ignored)
        self._closed = False
        if self.fleet.transport == "inproc":
            self._transport = InProcTransport()
            self._table = worker_lib.worker_fn_table(trainer)
        elif self.fleet.transport == "proc":
            self._validate_proc(trainer)
            self._transport = ProcTransport()
            self._table = None
        else:
            raise ValueError(
                f"unknown fleet transport {self.fleet.transport!r} "
                f"(expected 'inproc' or 'proc')")
        self._patch(trainer)
        for i in range(self.fleet.n_workers):
            self.spawn(f"w{i}")

    # -- setup ----------------------------------------------------------
    def _validate_proc(self, trainer):
        cfg = trainer.cfg
        if jax.default_backend() == "tpu":
            # a chip belongs to one process: this one holds it, so spawned
            # workers would fail or hang on it (or quietly use the CPU)
            raise ValueError("proc transport cannot run on a TPU backend: "
                             "its spawned workers would need the chip this "
                             "process holds — use transport='inproc', whose "
                             "thread workers share it")
        if self.fleet.worker_spec is None:
            raise ValueError("proc transport needs FleetConfig.worker_spec "
                             "(the worker-side trainer replica recipe)")
        if trainer.population is not None:
            raise ValueError("proc transport supports pinned trainers only "
                             "(the streamed population's prefetched device "
                             "cohorts cannot cross a process boundary)")
        if cfg.block_size > 1 or cfg.async_depth >= 1:
            raise ValueError("proc transport supports the per-round path "
                             "only (set block_size=1, async_depth=0)")

    def _patch(self, trainer):
        """Route the trainer's cached executor seams through the fleet.
        Everything else — staging, rng, cold start, eval, folds,
        checkpoints — keeps running on the coordinator, unchanged."""
        if self.fleet.transport == "inproc":
            # the real compiled closures live in self._table; jobs carry
            # their arguments by reference
            trainer._round_exec = self._proxy("round")
            trainer._block_exec = self._proxy("block")
            trainer._async_exec = self._proxy("async")
        else:
            trainer._round_exec = self._proxy("round", remote=True)
        trainer._fleet_meta = self._fleet_meta

    def _fleet_meta(self) -> dict:
        """The control-plane checkpoint snapshot (ckpt format v4 ``"fleet"``
        metadata): enough to resume the chaos/elasticity script clock and
        audit the fleet shape at save time."""
        return {"transport": self.fleet.transport,
                "n_workers": int(self.fleet.n_workers),
                "live": sorted(self._live),
                "dispatch_clock": int(self._clock),
                "next_job_id": int(self._job_id)}

    # -- fleet membership -----------------------------------------------
    def spawn(self, name: str):
        """Start (and eventually adopt) a worker. In-process workers share
        the coordinator's executor table; process workers build their own
        trainer replica from the ``WorkerSpec`` (their cold start) and
        join once it is up. Adoption happens when the ``join`` message is
        pumped — dispatches only ever go to adopted workers."""
        if self.fleet.transport == "inproc":
            ep = self._transport.add_worker(name)
            w = worker_lib.InProcWorker(name, ep, self._table,
                                        self.fleet.heartbeat_interval)
            self._workers[name] = w
            w.start()
        else:
            self._transport.add_worker(
                name, worker_lib.worker_entry, self.fleet.worker_spec,
                self.fleet.heartbeat_interval)

    def retire(self, name: str):
        """Graceful leave: stop dispatching to the worker and ask it to
        drain and exit; the ``leave`` message finalizes the departure."""
        if name in self._live:
            self._live.remove(name)
            self.obs.registry.set("fleet.workers", len(self._live))
        self._transport.send(name, Message("stop"))

    def kill_worker(self, name: str):
        """Hard-kill a worker (the chaos primitive): SIGKILL in process
        mode, a no-reply hard-stop in-process. Detection is the heartbeat
        monitor's job, not ours."""
        if self.fleet.transport == "inproc":
            w = self._workers.get(name)
            if w is not None:
                w.kill()
        else:
            self._transport.kill(name)

    def _adopt(self, name: str, now: float):
        if name in self._live:
            return
        self._live.append(name)
        self._monitor.add(name, now)
        self.obs.registry.inc("fleet.joins")
        self.obs.registry.set("fleet.workers", len(self._live))

    def _declare_dead(self, name: str):
        if name in self._live:
            self._live.remove(name)
        self.obs.registry.inc("fleet.worker_deaths")
        self.obs.registry.set("fleet.workers", len(self._live))

    def _on_leave(self, name: str):
        if name in self._live:
            self._live.remove(name)
        self._monitor.remove(name)
        self._workers.pop(name, None)
        self._transport.remove_worker(name)
        self.obs.registry.inc("fleet.leaves")
        self.obs.registry.set("fleet.workers", len(self._live))

    # -- the message pump -----------------------------------------------
    def _route(self, msg: Message, now: float):
        reg = self.obs.registry
        if msg.kind == "heartbeat":
            reg.inc("fleet.heartbeats")
            if self._monitor.beat(msg.src, now) \
                    and msg.src not in self._live:
                # back from the dead (a muted/delayed heartbeat window):
                # re-adopt — the resurrection path. ``beat`` only returns
                # True for a previously-adopted worker.
                self._live.append(msg.src)
                reg.inc("fleet.joins")
                reg.set("fleet.workers", len(self._live))
        elif msg.kind == "join":
            self._adopt(msg.src, now)
        elif msg.kind == "leave":
            self._on_leave(msg.src)
        elif msg.kind == "result":
            if msg.job_id in self._done or msg.job_id in self._results:
                # a superseded lease's late answer, or a chaos-duplicated
                # delivery: the first result won, this copy is ignored
                reg.inc("fleet.stale_results")
            else:
                self._results[msg.job_id] = msg.payload
        elif msg.kind == "error":
            raise RuntimeError(
                f"fleet worker {msg.src!r} failed job {msg.job_id}:\n"
                f"{msg.payload}")
        elif msg.kind == "eof":
            # closed pipe: the fast path of process-death detection. The
            # pipe must come out of the transport either way, or the
            # closed fd keeps signalling ready forever.
            self._transport.remove_worker(msg.src)
            if msg.src in self._live:
                with self.obs.span("heartbeat", worker=msg.src,
                                   event="eof"):
                    self._monitor.remove(msg.src)
                    self._declare_dead(msg.src)

    def _pump(self, timeout: float):
        """Drain every available message (blocking up to ``timeout`` for
        the first), then sweep the heartbeat monitor — drain-first keeps
        queued beats from reading as misses."""
        now = time.monotonic()
        msg = self._transport.recv(timeout)
        while msg is not None:
            for m in self._chaos.filter(msg, now):
                self._route(m, now)
            msg = self._transport.recv(0.0)
            now = time.monotonic()
        for name in self._monitor.sweep(time.monotonic()):
            self.obs.registry.inc("fleet.heartbeat_misses")
            with self.obs.span("heartbeat", worker=name, event="miss"):
                self._declare_dead(name)

    # -- dispatch -------------------------------------------------------
    def _elastic(self):
        """Apply the membership script for this dispatch-clock tick."""
        for name in (self.fleet.joins or {}).get(self._clock, ()):
            self.spawn(name)
        for name in (self.fleet.leaves or {}).get(self._clock, ()):
            self.retire(name)

    def _pick_worker(self) -> str:
        deadline = time.monotonic() + self.fleet.join_timeout
        while not self._live:
            if time.monotonic() > deadline:
                raise RuntimeError(
                    "fleet has no live workers (all dead or departed, and "
                    "none joined within join_timeout="
                    f"{self.fleet.join_timeout}s)")
            self._pump(0.01)
        w = self._live[self._rr % len(self._live)]
        self._rr += 1
        return w

    def _await_result(self, job_id: int, holder: str, deadline: float):
        """The lease wait: the result, or ``_MISSING`` when the lease must
        requeue (timeout, dropped message, or the holder died)."""
        while True:
            self._pump(0.005)
            if job_id in self._results:
                return self._results.pop(job_id)
            if job_id in self._chaos.dropped:
                # the transport lost the result: informationally a timeout,
                # resolved now instead of stalling out the full lease
                self._chaos.dropped.discard(job_id)
                return _MISSING
            if holder not in self._live:
                return _MISSING          # holder died: requeue immediately
            if time.monotonic() >= deadline:
                return _MISSING

    def _proxy(self, fn_name: str, remote: bool = False):
        """The executor seam: a callable with the real executor's
        signature that runs the job through lease + transport + fleet."""

        def dispatch(*args):
            spec = (self.fleet.faults.spec(self._clock)
                    if self.fleet.faults is not None else None)
            self._elastic()
            self._clock += 1
            payload = worker_lib._to_numpy(args) if remote else args
            lease = leases_lib.Lease(staged=(fn_name, payload))
            return self._dispatch_lease(lease, spec)

        return dispatch

    def _dispatch_lease(self, lease, spec):
        reg = self.obs.registry
        buf = leases_lib.RequeueBuffer()
        attempts = 0
        while True:
            holder = self._pick_worker()
            if spec is not None and getattr(spec, "worker_kill", False):
                # SIGKILL mid-dispatch: the holder dies with the job in
                # flight; heartbeat misses (or the closed pipe) detect it
                self.kill_worker(holder)
            if spec is not None and getattr(spec, "heartbeat_delay", 0.0):
                self._chaos.mute_heartbeats(
                    holder, time.monotonic() + float(spec.heartbeat_delay))
            job_id = self._job_id
            self._job_id += 1
            self._chaos.arm(spec, job_id)
            spec = None                  # chaos fires once per scripted tick
            reg.inc("fleet.jobs")
            lease.holder, lease.job_id = holder, job_id
            lease.deadline = self._policy.deadline(time.monotonic())
            with self.obs.span("lease", job=job_id, worker=holder,
                               attempt=attempts):
                sent = self._transport.send(
                    holder, Message("job", job_id=job_id,
                                    payload=lease.staged))
                result = (self._await_result(job_id, holder, lease.deadline)
                          if sent else _MISSING)
            self._done.add(job_id)
            if result is not _MISSING:
                reg.inc("fleet.results")
                return result
            # expired / lost / holder died: requeue with capped backoff
            # (raises "unrecoverable" after max_retries, like the async
            # runtime's cohort leases)
            reg.inc("fleet.lease_expiries")
            lease.attempts = attempts
            buf.push(lease, self._policy, time.monotonic(),
                     what="fleet job", timeout_key="lease_timeout",
                     retries_key="max_retries")
            reg.inc("fleet.requeues")
            ready = None
            while ready is None:
                wait = buf.earliest() - time.monotonic()
                if wait > 0:
                    self._pump(min(wait, 0.02))
                ready = buf.pop_ready(time.monotonic())
            _, attempts = ready

    # -- the run surface -------------------------------------------------
    def run(self, n_rounds=None):
        """Train through the fleet: the trainer's own loop, every device
        dispatch routed through a worker lease."""
        return self.trainer.run(n_rounds)

    def save_checkpoint(self, path: str | None = None) -> str:
        """Coordinator-owned checkpointing: the trainer's atomic v4
        snapshot, with this fleet's control-plane metadata riding along."""
        return self.trainer.save_checkpoint(path)

    def load_checkpoint(self, path_or_dir: str) -> int:
        """Coordinator restart: restore the trainer bit-identically and
        resume the control-plane script clock from the fleet metadata."""
        from repro.checkpoint import io as ckpt_io
        path = path_or_dir
        if os.path.isdir(path):
            path = ckpt_io.latest_checkpoint(path)
            if path is None:
                raise FileNotFoundError(
                    f"no ckpt_*.npz checkpoints in {path_or_dir}")
        t = self.trainer.load_checkpoint(path)
        fm = ckpt_io.load_metadata(path).get("fleet")
        if fm is not None:
            self._clock = int(fm["dispatch_clock"])
            self._job_id = int(fm["next_job_id"])
        return t

    def close(self):
        """Retire the fleet, close the transport, finalize the trainer."""
        if self._closed:
            return
        self._closed = True
        for name in list(self._live):
            self.retire(name)
        # give graceful leavers a moment to ack (hard-killed workers never
        # will — don't wait on them), then tear down
        deadline = time.monotonic() + 0.5
        while time.monotonic() < deadline:
            if all(w._dead.is_set() for w in self._workers.values()):
                break
            try:
                self._pump(0.02)
            except RuntimeError:
                break
        for w in list(self._workers.values()):
            w.kill()
        self._transport.close()
        self.trainer.close()
