"""Elastic coordinator/worker control plane: process-level fault domains,
heartbeat leases, and coordinator-owned recovery.

The load-bearing guarantees:

  * fleet-size-1 in-process mode is BIT-identical to ``engine.run()`` —
    History, params, group params, membership, local state, comm
    accounting and the rng stream — for all four frameworks, pinned and
    streamed. The control plane adds zero numerical surface.
  * recovery is bit-identical: a worker SIGKILLed (or hard-stopped)
    mid-dispatch is detected by missed heartbeats, its lease requeues
    with capped backoff, and the re-dispatched job produces the exact
    same run. Same for dropped / duplicated / reordered messages.
  * the fleet degrades gracefully down to one worker, adopts elastic
    newcomers mid-run, and a coordinator restart resumes bit-identically
    from the v4 checkpoint (fleet metadata riding along).
  * checkpoint integrity: per-array CRC32 checksums catch bit flips and
    torn archives at load (``CheckpointCorruptError``); pre-checksum v3
    archives still load; ``checkpoint_keep`` prunes old snapshots.
"""
import json
import os
import threading
import time
import zlib

import jax
import numpy as np
import pytest

from repro.checkpoint import io as ckpt_io
from repro.core.fedgroup import FedGroupTrainer
from repro.data.generators import mnist_like
from repro.fed import leases as leases_lib
from repro.fed.engine import FedAvgTrainer, FedConfig
from repro.fed.fesem import FeSEMTrainer
from repro.fed.ifca import IFCATrainer
from repro.fed.population import (FaultConfig, FaultSpec, Population,
                                  PopulationConfig)
from repro.fed.store import ArrayClientStore
from repro.launch.coordinator import Coordinator, FleetConfig
from repro.launch.transport import (ChaosRouter, HeartbeatMonitor,
                                    InProcTransport, Message)
from repro.launch.worker import WorkerSpec, synthetic_builder


@pytest.fixture(scope="module")
def small_data():
    return mnist_like(seed=0, n_clients=40, classes_per_client=2,
                      total_train=2000, dim=16)


@pytest.fixture(scope="module")
def small_model():
    from repro.models.paper_models import mclr
    return mclr(16, 10)


def _cfg(**kw):
    base = dict(n_rounds=4, clients_per_round=8, local_epochs=2,
                batch_size=5, lr=0.05, n_groups=3, pretrain_scale=4, seed=0)
    base.update(kw)
    return FedConfig(**base)


def _assert_tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


STREAM_KW = dict(initial_active=30, arrival_rate=2.0, prefetch=2)


def _fresh(cls, model, data, streamed, **cfg_kw):
    cfg = _cfg(**cfg_kw)
    if streamed:
        pop = Population(ArrayClientStore(data),
                         PopulationConfig(**STREAM_KW))
        return cls(model, None, cfg, population=pop)
    return cls(model, data, cfg)


def _assert_same_run(fleet_tr, ref_tr, h_fleet, h_ref):
    """The full bit-identity surface: history, params, clustered state,
    local state, comm accounting, rng stream."""
    assert h_fleet.rounds == h_ref.rounds
    _assert_tree_equal(fleet_tr.params, ref_tr.params)
    if hasattr(ref_tr, "group_params"):
        _assert_tree_equal(fleet_tr.group_params, ref_tr.group_params)
        np.testing.assert_array_equal(fleet_tr.membership,
                                      ref_tr.membership)
    if getattr(ref_tr, "local_flat", None) is not None:
        np.testing.assert_array_equal(np.asarray(fleet_tr.local_flat),
                                      np.asarray(ref_tr.local_flat))
    assert fleet_tr.comm_params == ref_tr.comm_params
    np.testing.assert_array_equal(np.asarray(fleet_tr.key),
                                  np.asarray(ref_tr.key))


def _fleet_snap(tr):
    reg = tr.obs.registry
    return {k: reg.get(k) for k in reg.names("fleet.")}


ALL_TRAINERS = [FedAvgTrainer, FedGroupTrainer, IFCATrainer, FeSEMTrainer]

# chaos-friendly knobs: in-process workers answer in ms, so short backoffs
# keep the chaos tests fast (drop-chaos expiry is signalled, not wall-clock
# timed). The heartbeat window stays a generous 0.6s — a beat thread stalled
# behind a jit compile must never read as a spurious death.
FAST = dict(heartbeat_interval=0.02, heartbeat_miss=30,
            backoff=0.005, backoff_cap=0.02)


# ---------------------------------------------------------------------------
# lease primitives (fed/leases.py)
# ---------------------------------------------------------------------------
class TestLeasePrimitives:
    def test_backoff_is_capped_exponential(self):
        assert leases_lib.backoff_delay(0, 0.05, 1.0) == 0.05
        assert leases_lib.backoff_delay(1, 0.05, 1.0) == 0.1
        assert leases_lib.backoff_delay(10, 0.05, 1.0) == 1.0

    def test_requeue_buffer_fifo_among_ready(self):
        buf = leases_lib.RequeueBuffer()
        pol = leases_lib.RetryPolicy(timeout=1.0, max_retries=5,
                                     backoff=0.0, backoff_cap=0.0)
        for staged in ("a", "b"):
            buf.push(leases_lib.Lease(staged=staged), pol, now=0.0)
        assert len(buf) == 2
        assert buf.pop_ready(0.0) == ("a", 1)      # FIFO among ready
        assert buf.pop_ready(0.0) == ("b", 1)
        assert buf.pop_ready(0.0) is None
        assert buf.earliest() is None

    def test_backoff_delays_readiness(self):
        buf = leases_lib.RequeueBuffer()
        pol = leases_lib.RetryPolicy(backoff=0.5, backoff_cap=10.0)
        buf.push(leases_lib.Lease(staged="x", attempts=1), pol, now=0.0)
        assert buf.pop_ready(0.9) is None          # 0.5 * 2^1 = 1.0
        assert buf.earliest() == 1.0
        assert buf.pop_ready(1.0) == ("x", 2)

    def test_exhausted_budget_raises_with_callers_key_names(self):
        buf = leases_lib.RequeueBuffer()
        pol = leases_lib.RetryPolicy(timeout=2.0, max_retries=1)
        lease = leases_lib.Lease(staged="x", attempts=1)
        with pytest.raises(RuntimeError, match=r"fleet job lease expired "
                           r".*lease_timeout=2.0s.*max_retries=1.*"
                           r"unrecoverable"):
            buf.push(lease, pol, now=0.0, what="fleet job",
                     timeout_key="lease_timeout", retries_key="max_retries")
        # the engine's default keys are unchanged
        with pytest.raises(RuntimeError, match="async_lease_timeout"):
            buf.push(leases_lib.Lease(staged="y", attempts=1), pol, now=0.0)


# ---------------------------------------------------------------------------
# heartbeat failure detection
# ---------------------------------------------------------------------------
class TestHeartbeatMonitor:
    def test_miss_threshold_and_resurrection(self):
        m = HeartbeatMonitor(interval=1.0, miss=3)
        m.add("w0", now=0.0)
        assert m.sweep(2.9) == []                  # inside the window
        assert m.sweep(3.1) == ["w0"]              # 3 missed beats: dead
        assert m.sweep(3.2) == []                  # declared only once
        assert m.is_dead("w0")
        assert m.beat("w0", 3.3) is True           # late beat resurrects
        assert not m.is_dead("w0")
        assert m.sweep(3.4) == []

    def test_beat_from_unknown_worker_is_ignored(self):
        m = HeartbeatMonitor(interval=1.0, miss=3)
        assert m.beat("ghost", 0.0) is False
        assert m.sweep(100.0) == []

    def test_removed_worker_never_declared(self):
        m = HeartbeatMonitor(interval=1.0, miss=2)
        m.add("w0", 0.0)
        m.remove("w0")
        assert m.sweep(100.0) == []
        assert m.beat("w0", 100.0) is False        # departed, not dead


# ---------------------------------------------------------------------------
# scripted delivery chaos
# ---------------------------------------------------------------------------
class TestChaosRouter:
    def test_drop_consumes_and_signals(self):
        c = ChaosRouter()
        c.arm(FaultSpec(msg_drop=True), job_id=7)
        out = c.filter(Message("result", "w0", 7, "payload"), now=0.0)
        assert out == [] and 7 in c.dropped
        # only that one delivery: a re-dispatched job 8 passes through
        out = c.filter(Message("result", "w0", 8, "payload"), now=0.0)
        assert [m.job_id for m in out] == [8]

    def test_dup_delivers_twice(self):
        c = ChaosRouter()
        c.arm(FaultSpec(msg_dup=True), job_id=3)
        out = c.filter(Message("result", "w0", 3, "p"), now=0.0)
        assert [m.job_id for m in out] == [3, 3]

    def test_reorder_holds_until_next_message_passes(self):
        c = ChaosRouter()
        c.arm(FaultSpec(msg_reorder=True), job_id=5)
        assert c.filter(Message("result", "w0", 5, "p"), now=0.0) == []
        out = c.filter(Message("heartbeat", "w1"), now=0.0)
        assert [(m.kind, m.job_id) for m in out] == \
            [("heartbeat", -1), ("result", 5)]

    def test_heartbeat_mute_until_deadline(self):
        c = ChaosRouter()
        c.mute_heartbeats("w0", until=1.0)
        assert c.filter(Message("heartbeat", "w0"), now=0.5) == []
        assert len(c.filter(Message("heartbeat", "w0"), now=1.5)) == 1
        # the mute is consumed: later beats flow
        assert len(c.filter(Message("heartbeat", "w0"), now=1.6)) == 1


class TestInProcTransport:
    def test_roundtrip_and_unknown_worker(self):
        tr = InProcTransport()
        ep = tr.add_worker("w0")
        assert tr.send("w0", Message("job", job_id=1)) is True
        assert ep.recv(0.1).job_id == 1
        ep.send(Message("result", "w0", 1, "r"))
        assert tr.recv(0.1).payload == "r"
        assert tr.recv(0.01) is None
        tr.remove_worker("w0")
        assert tr.send("w0", Message("job")) is False
        with pytest.raises(ValueError, match="already registered"):
            tr.add_worker("w0"), tr.add_worker("w0")


# ---------------------------------------------------------------------------
# fleet-size-1 bit-identity (the tentpole equivalence anchor)
# ---------------------------------------------------------------------------
class TestFleetOneBitIdentity:
    @pytest.mark.parametrize("streamed", [False, True],
                             ids=["pinned", "streamed"])
    @pytest.mark.parametrize("cls", ALL_TRAINERS,
                             ids=lambda c: c.framework)
    def test_fleet_of_one_equals_engine_run(self, cls, streamed,
                                            small_model, small_data):
        ref = _fresh(cls, small_model, small_data, streamed)
        h_ref = ref.run()
        ref.close()

        tr = _fresh(cls, small_model, small_data, streamed)
        coord = Coordinator(tr, FleetConfig(n_workers=1))
        h = coord.run()
        snap = _fleet_snap(tr)
        coord.close()

        _assert_same_run(tr, ref, h, h_ref)
        assert snap["fleet.jobs"] == snap["fleet.results"] > 0
        assert snap["fleet.heartbeats"] > 0

    def test_async_path_routes_through_fleet(self, small_model, small_data):
        tr = FedAvgTrainer(small_model, small_data,
                           _cfg(async_depth=2, async_alpha=0.5))
        coord = Coordinator(tr, FleetConfig(n_workers=1))
        h = coord.run()
        snap = _fleet_snap(tr)
        coord.close()
        assert len(h.rounds) == 4
        assert all(np.isfinite(np.asarray(leaf)).all()
                   for leaf in jax.tree_util.tree_leaves(tr.params))
        assert snap["fleet.jobs"] >= 4          # async dispatches routed

    def test_rejects_unknown_transport(self, small_model, small_data):
        tr = FedAvgTrainer(small_model, small_data, _cfg())
        with pytest.raises(ValueError, match="unknown fleet transport"):
            Coordinator(tr, FleetConfig(transport="carrier-pigeon"))
        tr.close()


# ---------------------------------------------------------------------------
# chaos recovery (in-process fault domains)
# ---------------------------------------------------------------------------
class TestChaosRecovery:
    def _ref(self, small_model, small_data, n_rounds=6):
        ref = _fresh(FedAvgTrainer, small_model, small_data, False,
                     n_rounds=n_rounds)
        h_ref = ref.run()
        ref.close()
        return ref, h_ref

    def test_worker_kill_recovers_bit_identically(self, small_model,
                                                  small_data):
        ref, h_ref = self._ref(small_model, small_data)
        faults = FaultConfig(rounds={1: FaultSpec(worker_kill=True)})
        tr = _fresh(FedAvgTrainer, small_model, small_data, False,
                    n_rounds=6)
        coord = Coordinator(tr, FleetConfig(n_workers=2, faults=faults,
                                            **FAST))
        h = coord.run()
        snap = _fleet_snap(tr)
        coord.close()
        _assert_same_run(tr, ref, h, h_ref)
        assert snap["fleet.worker_deaths"] == 1
        assert snap["fleet.lease_expiries"] >= 1
        assert snap["fleet.requeues"] >= 1
        assert snap["fleet.workers"] == 1       # degraded, still finished

    def test_message_chaos_is_bit_identical(self, small_model, small_data):
        # drop, duplicate and reorder the result message on three
        # different rounds of one run: every delivery fault is absorbed
        ref, h_ref = self._ref(small_model, small_data)
        faults = FaultConfig(rounds={1: FaultSpec(msg_drop=True),
                                     2: FaultSpec(msg_dup=True),
                                     3: FaultSpec(msg_reorder=True)})
        tr = _fresh(FedAvgTrainer, small_model, small_data, False,
                    n_rounds=6)
        coord = Coordinator(tr, FleetConfig(n_workers=2, faults=faults,
                                            **FAST))
        h = coord.run()
        snap = _fleet_snap(tr)
        coord.close()
        _assert_same_run(tr, ref, h, h_ref)
        assert snap["fleet.msgs_dropped"] == 1
        assert snap["fleet.msgs_duplicated"] == 1
        assert snap["fleet.msgs_reordered"] == 1
        assert snap["fleet.requeues"] == 1      # only the drop requeues
        assert snap["fleet.stale_results"] >= 1  # the dup's second copy

    def test_heartbeat_delay_death_and_resurrection(self, small_model,
                                                    small_data):
        # mute a healthy worker's beats past the miss window while it
        # works a (stalled) job: it is declared dead, the lease requeues
        # to the survivor, then the worker's first unmuted beat resurrects
        # it — and the run is still bit-identical
        ref, h_ref = self._ref(small_model, small_data)
        faults = FaultConfig(rounds={1: FaultSpec(heartbeat_delay=1.2)})
        tr = _fresh(FedAvgTrainer, small_model, small_data, False,
                    n_rounds=6)
        coord = Coordinator(tr, FleetConfig(n_workers=2, faults=faults,
                                            **FAST))
        real = coord._table["round"]
        calls = []

        def stall_second_call(*args):
            calls.append(1)
            if len(calls) == 2:         # the muted worker's job: outlive
                time.sleep(0.9)         # the 0.6s miss window
            return real(*args)

        coord._table["round"] = stall_second_call
        h = coord.run()
        snap = _fleet_snap(tr)
        # the muted worker is healthy: once the mute lapses its next beat
        # must resurrect it
        deadline = time.monotonic() + 3.0
        while len(coord._live) < 2 and time.monotonic() < deadline:
            coord._pump(0.02)
        resurrected = len(coord._live)
        joins = tr.obs.registry.get("fleet.joins")
        coord.close()
        _assert_same_run(tr, ref, h, h_ref)
        assert snap["fleet.worker_deaths"] == 1
        assert snap["fleet.heartbeat_misses"] == 1
        assert snap["fleet.requeues"] >= 1
        assert resurrected == 2 and joins == 3  # w0, w1, 1 resurrection

    def test_elastic_join_and_leave(self, small_model, small_data):
        ref, h_ref = self._ref(small_model, small_data)
        tr = _fresh(FedAvgTrainer, small_model, small_data, False,
                    n_rounds=6)
        coord = Coordinator(tr, FleetConfig(
            n_workers=1, joins={2: ["newcomer"]}, leaves={4: ["w0"]},
            **FAST))
        h = coord.run()
        snap = _fleet_snap(tr)
        coord.close()
        _assert_same_run(tr, ref, h, h_ref)
        assert snap["fleet.joins"] == 2         # w0 + the newcomer
        assert snap["fleet.leaves"] == 1
        assert snap["fleet.workers"] == 1       # only the newcomer left

    def test_lease_timeout_requeues_to_next_worker(self, small_model,
                                                   small_data):
        # a worker that stalls (but does not die) past the lease deadline:
        # the lease expires, requeues, and the re-dispatched job lands on
        # the other worker — run still bit-identical
        ref, h_ref = self._ref(small_model, small_data, n_rounds=2)
        tr = _fresh(FedAvgTrainer, small_model, small_data, False,
                    n_rounds=2)
        coord = Coordinator(tr, FleetConfig(n_workers=2, lease_timeout=0.4,
                                            **FAST))
        real = coord._table["round"]
        # compile the executor up front (state-free call): the requeued job
        # must answer well inside its own 0.4 s lease
        x, y, n = tr._client_batch(np.arange(8))
        jax.block_until_ready(real(
            jax.tree_util.tree_map(lambda p: p[None], tr.params),
            np.zeros(8, np.int32), x, y, n,
            jax.random.split(jax.random.PRNGKey(0), 8)))
        calls = []
        requeued_done = threading.Event()

        def stall_once(*args):
            calls.append(1)
            if len(calls) == 1:
                # hold the first job until its lease expired and the
                # requeued copy was answered by the other worker
                assert requeued_done.wait(timeout=120.0)
                return real(*args)
            out = real(*args)
            requeued_done.set()
            return out

        coord._table["round"] = stall_once
        h = coord.run()
        snap = _fleet_snap(tr)
        coord.close()
        _assert_same_run(tr, ref, h, h_ref)
        assert snap["fleet.lease_expiries"] >= 1
        assert snap["fleet.requeues"] >= 1

    def test_unrecoverable_job_raises_with_fleet_keys(self, small_model,
                                                      small_data):
        tr = _fresh(FedAvgTrainer, small_model, small_data, False,
                    n_rounds=2)
        coord = Coordinator(tr, FleetConfig(n_workers=1, lease_timeout=0.1,
                                            max_retries=1, **FAST))
        coord._table["round"] = lambda *a: time.sleep(5.0)
        with pytest.raises(RuntimeError, match=r"fleet job lease expired"
                           r".*lease_timeout=0.1s.*max_retries=1"):
            coord.run()
        coord.close()

    def test_worker_exception_surfaces_with_traceback(self, small_model,
                                                      small_data):
        tr = _fresh(FedAvgTrainer, small_model, small_data, False,
                    n_rounds=2)
        coord = Coordinator(tr, FleetConfig(n_workers=1, **FAST))

        def boom(*args):
            raise ValueError("kaboom in the executor")

        coord._table["round"] = boom
        with pytest.raises(RuntimeError,
                           match=r"(?s)failed job 0.*kaboom in the executor"):
            coord.run()
        coord.close()


# ---------------------------------------------------------------------------
# coordinator restart: kill-and-resume through the control plane
# ---------------------------------------------------------------------------
class TestCoordinatorRestart:
    def test_restart_resumes_bit_identically(self, small_model, small_data,
                                             tmp_path):
        ref = _fresh(FedGroupTrainer, small_model, small_data, True)
        h_ref = ref.run(4)
        ref.close()

        ck = dict(checkpoint_every=2, checkpoint_dir=str(tmp_path))
        killed = _fresh(FedGroupTrainer, small_model, small_data, True,
                        **ck)
        c1 = Coordinator(killed, FleetConfig(n_workers=2, **FAST))
        c1.run(3)                          # "killed" after 3 rounds
        c1.close()
        path = ckpt_io.checkpoint_path(str(tmp_path), 2)
        assert os.path.exists(path)
        # the v4 archive carries the control-plane snapshot
        fm = ckpt_io.load_metadata(path)["fleet"]
        assert fm["transport"] == "inproc"
        assert fm["n_workers"] == 2 and len(fm["live"]) == 2
        assert fm["dispatch_clock"] >= 2

        resumed = _fresh(FedGroupTrainer, small_model, small_data, True,
                         **ck)
        c2 = Coordinator(resumed, FleetConfig(n_workers=2, **FAST))
        t = c2.load_checkpoint(str(tmp_path))      # dir -> latest ckpt
        assert t == 2
        assert c2._clock == fm["dispatch_clock"]   # script clock resumes
        h_res = c2.run(4 - t)
        c2.close()

        assert h_res.rounds == h_ref.rounds
        _assert_tree_equal(resumed.group_params, ref.group_params)
        np.testing.assert_array_equal(resumed.membership, ref.membership)
        assert resumed.comm_params == ref.comm_params
        np.testing.assert_array_equal(np.asarray(resumed.key),
                                      np.asarray(ref.key))

    def test_plain_trainer_reads_fleet_checkpoint(self, small_model,
                                                  small_data, tmp_path):
        # a fleet-run checkpoint restores into a coordinator-less trainer:
        # the fleet metadata and metric snapshot ride along harmlessly
        tr = _fresh(FedAvgTrainer, small_model, small_data, False)
        coord = Coordinator(tr, FleetConfig(n_workers=1))
        coord.run(2)
        path = coord.save_checkpoint(str(tmp_path / "ck.npz"))
        coord.close()

        solo = _fresh(FedAvgTrainer, small_model, small_data, False)
        assert solo.load_checkpoint(path) == 2
        solo.run(1)
        assert len(solo.history.rounds) == 3
        solo.close()


# ---------------------------------------------------------------------------
# process-level fault domains (spawned workers, SIGKILL chaos)
# ---------------------------------------------------------------------------
PROC_KW = dict(framework="fedavg", n_clients=20, dim=8, seed=0, n_rounds=3,
               clients_per_round=6)


@pytest.mark.fleet
class TestProcFleet:
    def test_sigkill_mid_dispatch_recovers_bit_identically(self):
        # the real thing: two spawned worker processes, one SIGKILLed
        # while it holds round 1's lease; the closed pipe / missed
        # heartbeats detect it, the lease requeues to the survivor, and
        # the run completes bit-identical to a single-process run
        ref = synthetic_builder(**PROC_KW)
        h_ref = ref.run()
        ref.close()

        tr = synthetic_builder(**PROC_KW)
        coord = Coordinator(tr, FleetConfig(
            n_workers=2, transport="proc",
            worker_spec=WorkerSpec("repro.launch.worker:synthetic_builder",
                                   PROC_KW),
            faults=FaultConfig(rounds={1: FaultSpec(worker_kill=True)}),
            heartbeat_interval=0.1, heartbeat_miss=5,
            lease_timeout=300.0, join_timeout=300.0))
        h = coord.run()
        snap = _fleet_snap(tr)
        coord.close()

        _assert_same_run(tr, ref, h, h_ref)
        assert snap["fleet.worker_deaths"] == 1
        assert snap["fleet.requeues"] >= 1
        assert snap["fleet.workers"] == 1

    def test_proc_mode_validates_its_limits(self, small_model, small_data):
        spec = WorkerSpec("repro.launch.worker:synthetic_builder", PROC_KW)
        pinned = _fresh(FedAvgTrainer, small_model, small_data, False)
        with pytest.raises(ValueError,
                           match="needs FleetConfig.worker_spec"):
            Coordinator(pinned, FleetConfig(transport="proc"))
        pinned.close()
        streamed = _fresh(FedAvgTrainer, small_model, small_data, True)
        with pytest.raises(ValueError, match="pinned trainers only"):
            Coordinator(streamed,
                        FleetConfig(transport="proc", worker_spec=spec))
        streamed.close()
        asy = _fresh(FedAvgTrainer, small_model, small_data, False,
                     async_depth=2)
        with pytest.raises(ValueError, match="per-round path only"):
            Coordinator(asy,
                        FleetConfig(transport="proc", worker_spec=spec))
        asy.close()

    def test_proc_mode_refuses_a_tpu_backend(self, small_model, small_data,
                                             monkeypatch):
        # one process per chip: spawned workers cannot share the chip the
        # coordinator's process holds, so the refusal comes before any spawn
        import repro.launch.coordinator as coord_mod
        tr = _fresh(FedAvgTrainer, small_model, small_data, False)
        spawned = []
        monkeypatch.setattr(coord_mod.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(coord_mod.ProcTransport, "add_worker",
                            lambda *a, **k: spawned.append(a))
        spec = WorkerSpec("repro.launch.worker:synthetic_builder", PROC_KW)
        with pytest.raises(ValueError, match="transport='inproc'"):
            Coordinator(tr, FleetConfig(transport="proc", worker_spec=spec))
        assert spawned == []
        tr.close()

    def test_bad_builder_spec_is_rejected(self):
        from repro.launch.worker import resolve_builder
        with pytest.raises(ValueError, match="module:function"):
            resolve_builder(WorkerSpec("no_colon_here"))


# ---------------------------------------------------------------------------
# checkpoint integrity (satellites: CRC32, retention, v3 compat)
# ---------------------------------------------------------------------------
class TestCheckpointIntegrity:
    def test_bit_flip_raises_corrupt_error(self, tmp_path):
        # a stored array whose bytes no longer match the save-time CRC32
        # table must fail loudly, never restore garbage
        path = str(tmp_path / "ck.npz")
        arr = np.arange(8, dtype=np.float32)
        meta = {ckpt_io._FORMAT_KEY: ckpt_io.CKPT_FORMAT_VERSION,
                ckpt_io._CRC_KEY: {"a": zlib.crc32(arr.tobytes()) ^ 0xFF}}
        with open(path, "wb") as f:
            np.savez(f, __meta__=json.dumps(meta), a=arr)
        with pytest.raises(ckpt_io.CheckpointCorruptError,
                           match="failed its CRC32"):
            ckpt_io.load_pytree(path, {"a": arr})

    def test_truncated_archive_raises_corrupt_error(self, tmp_path):
        path = str(tmp_path / "ck.npz")
        ckpt_io.save_pytree(path, {"a": np.arange(64, dtype=np.float32)})
        raw = open(path, "rb").read()
        open(path, "wb").write(raw[:len(raw) // 2])
        with pytest.raises(ckpt_io.CheckpointCorruptError):
            ckpt_io.load_pytree(path, {"a": np.zeros(64, np.float32)})

    def test_intact_roundtrip_and_crc_is_internal(self, tmp_path):
        path = str(tmp_path / "ck.npz")
        tree = {"a": np.arange(4.0), "b": np.ones((2, 3))}
        ckpt_io.save_pytree(path, tree, {"note": "x"})
        _assert_tree_equal(ckpt_io.load_pytree(path, tree), tree)
        # the checksum table never leaks into user metadata
        assert ckpt_io.load_metadata(path) == {"note": "x"}

    def test_pre_checksum_v3_archive_still_loads(self, tmp_path):
        path = str(tmp_path / "old.npz")
        arr = np.arange(8, dtype=np.float32)
        meta = {ckpt_io._FORMAT_KEY: 3}      # v3: no __crc__ table
        with open(path, "wb") as f:
            np.savez(f, __meta__=json.dumps(meta), a=arr)
        _assert_tree_equal(ckpt_io.load_pytree(path, {"a": arr}),
                           {"a": arr})

    def test_prune_keeps_newest_n(self, tmp_path):
        for t in (2, 4, 6, 8):
            ckpt_io.save_pytree(ckpt_io.checkpoint_path(str(tmp_path), t),
                                {"a": np.zeros(2)})
        keeper = str(tmp_path / "notes.txt")
        open(keeper, "w").write("not a checkpoint")
        removed = ckpt_io.prune_checkpoints(str(tmp_path), keep=2)
        assert sorted(os.path.basename(p) for p in removed) == \
            ["ckpt_00000002.npz", "ckpt_00000004.npz"]
        assert os.path.exists(ckpt_io.checkpoint_path(str(tmp_path), 8))
        assert os.path.exists(keeper)        # non-checkpoints untouched
        assert ckpt_io.prune_checkpoints(str(tmp_path), keep=0) == []

    def test_checkpoint_keep_prunes_during_run(self, small_model,
                                               small_data, tmp_path):
        tr = _fresh(FedAvgTrainer, small_model, small_data, False,
                    checkpoint_every=1, checkpoint_dir=str(tmp_path),
                    checkpoint_keep=2)
        tr.run(4)
        tr.close()
        names = sorted(p.name for p in tmp_path.glob("ckpt_*.npz"))
        assert names == ["ckpt_00000003.npz", "ckpt_00000004.npz"]
        # the survivor restores fine
        resumed = _fresh(FedAvgTrainer, small_model, small_data, False,
                         checkpoint_every=1, checkpoint_dir=str(tmp_path),
                         checkpoint_keep=2)
        assert resumed.load_checkpoint(str(tmp_path)) == 4
        resumed.close()


# ---------------------------------------------------------------------------
# quarantine edge case (satellite: all-screened round = identity fold)
# ---------------------------------------------------------------------------
class TestEmptyFold:
    def test_all_screened_round_is_identity_passthrough(self, small_model,
                                                        small_data):
        faults = FaultConfig(
            rounds={1: FaultSpec(corrupt=8, corrupt_mode="nan")})
        pop = Population(ArrayClientStore(small_data),
                         PopulationConfig(faults=faults, **STREAM_KW))
        tr = FedGroupTrainer(small_model, None,
                             _cfg(quarantine=True), population=pop)
        tr.run(1)
        before = jax.tree_util.tree_map(
            lambda a: np.asarray(a).copy(), tr.group_params)
        h = tr.run(1)                        # round 1: whole cohort NaN
        after = jax.tree_util.tree_map(np.asarray, tr.group_params)
        assert h.rounds[1].quarantined == 8  # every lane screened
        _assert_tree_equal(after, before)    # fold was the identity
        assert tr.obs.registry.get("rounds.empty_folds") == 1
        h2 = tr.run(2)                       # healthy rounds keep training
        assert tr.obs.registry.get("rounds.empty_folds") == 1
        assert h2.rounds[2].quarantined == 0
        tr.close()
