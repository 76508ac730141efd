#!/usr/bin/env python3
"""Smoke run of FedGroup on TPU at the paper's FEMNIST MLP-512 width.

    python3 chip_smoke.py              # one chip: phases per-round, block, madc
    python3 chip_smoke.py --chips 4    # a four-chip host: phase mesh only

The setting is the paper's FEMNIST row of Table 2: 200 writer-clients with
18,345 training samples of 784 features over 26 classes (``femnist_like``),
the MLP-512 model (d_w = 415,258), and FedGroup with the EDC measure at the
``FedConfig`` defaults K=20, E=20, B=10, m=3, alpha=20. Data and weights
come from the seed. Every phase drives the trainer API that
``launch/train.py`` drives (``FedGroupTrainer``, ``run``,
``group_cold_start``).

  per-round  group cold start + 3 rounds on the per-round executor.
  block      4 rounds with block_size=4 against the same seed run round by
             round; accuracies agree within ACC_TOL. The cold start here
             assigns every client (alpha = ceil(200 / m)): at alpha=20 it
             assigns 60 of 200, every later cohort holds newcomers, and a
             newcomer needs the host between rounds, so no scan block would
             ever form. Round 0 runs alone (the cold start), rounds 1-3 as
             one scan block with the carry donated.
  madc       a MADC cold start at m=5, alpha=30 (n = 150, at or above the
             compiled kernel's crossover, so the trainer runs the Pallas
             MADC kernel); then ``madc_block`` against the jnp reference at
             n = 150 and ``cosine_block`` against its f32 reference at
             (60, 415,258, 3), each within KERNEL_TOL.
  mesh       (--chips 4) 3 FedGroup rounds on the auto-detected (4,) data
             mesh and on a (2, 2) (data, model) mesh, each against the
             single-device executor on the first chip after every round:
             accuracies within MESH_ACC_TOL, group parameters within
             PARAM_RTOL relative drift.

Every line before the last is a smoke-run reading (wall and compile
seconds, persistent-cache hits, peak device memory), not a benchmark
metric. The last line is the verdict, printed only when every phase passed
on a TPU:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any non-finite value, comparison out of tolerance, failed phase or backend
other than ``tpu`` exits nonzero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro.core import measures                                 # noqa: E402
from repro.core.fedgroup import FedGroupTrainer                 # noqa: E402
from repro.core.svd import randomized_truncated_svd             # noqa: E402
from repro.data.generators import femnist_like                  # noqa: E402
from repro.fed.engine import FedConfig                          # noqa: E402
from repro.kernels import ops, ref                              # noqa: E402
from repro.launch.compile_cache import enable_compile_cache     # noqa: E402
from repro.launch.mesh import make_fed_mesh                     # noqa: E402
from repro.models.paper_models import mlp                       # noqa: E402

ACC_TOL = 2e-3       # block vs per-round |weighted_acc difference| per round
KERNEL_TOL = 1e-4    # max |kernel - reference|
# Meshes vs one device. A mesh sums client updates (and, on the model axis,
# contractions) in another order, and 640 SGD steps a round carry a
# last-bit difference on: the drift grows about tenfold a round (a (2, 2)
# mesh of host devices: 2e-6, 1e-5, 1e-4 of ||params||), while a round's
# own update is ~2e-2 of ||params||. The bounds sit at half of that, so a
# lost all-reduce or a wrong cohort, which moves a round's worth, fails.
MESH_ACC_TOL = 1e-2  # |weighted_acc difference| per round
PARAM_RTOL = 1e-2    # ||group params - 1-device group params|| / ||...||


@dataclasses.dataclass(frozen=True)
class Setting:
    """Sizes of one smoke run; the defaults are the paper's FEMNIST
    MLP-512 setting."""
    n_clients: int = 200
    total_train: int = 18345
    dim: int = 784
    hidden: int = 512
    n_classes: int = 26
    fed: FedConfig = dataclasses.field(default_factory=FedConfig)
    madc_groups: int = 5
    madc_alpha: int = 30
    edc_rows: int = 60

    @property
    def d_w(self) -> int:
        return ((self.dim + 1) * self.hidden
                + (self.hidden + 1) * self.n_classes)

    def data(self):
        return femnist_like(self.fed.seed, n_clients=self.n_clients,
                            total_train=self.total_train, dim=self.dim,
                            n_classes=self.n_classes)

    def model(self):
        return mlp(self.dim, self.hidden, self.n_classes)

    def cfg(self, **kw) -> FedConfig:
        return dataclasses.replace(self.fed, **kw)


class SmokeFailure(Exception):
    """A phase produced a non-finite value or a comparison out of
    tolerance."""


def _check(ok, what: str):
    if not ok:
        raise SmokeFailure(what)


def _rounds(tag: str, history):
    """Print and check one run's per-round readings -> (acc, disc)."""
    acc = np.array([r.weighted_acc for r in history.rounds])
    disc = np.array([r.discrepancy for r in history.rounds])
    for r in history.rounds:
        print(f"  {tag} round {r.round}: weighted_acc={r.weighted_acc!r} "
              f"discrepancy={r.discrepancy!r}")
    _check(np.isfinite(acc).all() and np.isfinite(disc).all(),
           f"{tag}: non-finite round metrics")
    return acc, disc


def _leaves(tree) -> list:
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _finite(tag: str, tree):
    _check(all(np.isfinite(x).all() for x in _leaves(tree)),
           f"{tag}: non-finite group parameters")


# ---------------------------------------------------------------------------
# phases: each raises SmokeFailure, or returns facts for main() to check
# ---------------------------------------------------------------------------
def per_round_phase(s: Setting, data, model) -> dict:
    tr = FedGroupTrainer(model, data, s.cfg())
    _check(tr.model_size == s.d_w,
           f"model has d_w={tr.model_size}, expected {s.d_w}")
    print(f"  d_w={tr.model_size} clients={data.n_clients} "
          f"K={tr.cfg.clients_per_round} E={tr.cfg.local_epochs} "
          f"B={tr.cfg.batch_size} m={tr.m} alpha={tr.cfg.pretrain_scale}")
    h = tr.run(3)
    _rounds("per-round", h)
    _finite("per-round", tr.group_params)
    tr.close()
    return {}


def block_phase(s: Setting, data, model) -> dict:
    alpha = -(-s.n_clients // s.fed.n_groups)     # assigns every client
    acc = {}
    for b in (1, 4):
        tr = FedGroupTrainer(model, data,
                             s.cfg(pretrain_scale=alpha, block_size=b))
        h = tr.run(4)
        acc[b], _ = _rounds(f"block_size={b}", h)
        _finite(f"block_size={b}", tr.group_params)
        if b > 1:
            # the scan-block executor is built on its first dispatch
            _check(tr._block_exec is not None,
                   "block_size=4 never dispatched a scan block")
        tr.close()
    diff = float(np.max(np.abs(acc[1] - acc[4])))
    print(f"  block vs per-round: max |acc diff| = {diff!r} "
          f"(tolerance {ACC_TOL})")
    _check(diff <= ACC_TOL, f"block vs per-round accuracy diff {diff!r}")
    return {}


def madc_phase(s: Setting, data, model) -> dict:
    m = s.madc_groups
    tr = FedGroupTrainer(model, data, s.cfg(
        n_groups=m, pretrain_scale=s.madc_alpha, measure="madc"))
    pre_idx, labels = tr.group_cold_start()
    n = len(pre_idx)
    print(f"  madc cold start: n={n} group sizes="
          f"{np.bincount(labels, minlength=m).tolist()}")
    _finite("madc cold start", tr.group_params)
    tr.close()

    # the kernels against their references on cold-start-shaped updates
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(s.fed.seed), 4)
    owner = jax.random.randint(k2, (n,), 0, m)
    dW = (jax.random.normal(k1, (m, s.d_w))[owner]
          + jax.random.normal(k3, (n, s.d_w)))
    M = measures.cosine_similarity_matrix(dW)
    err_madc = float(jnp.max(jnp.abs(
        ops.madc_block(M) - measures.madc(M, use_kernel=False))))
    rows = dW[:s.edc_rows]
    V = randomized_truncated_svd(rows.T, s.fed.n_groups, key=k4)
    err_edc = float(jnp.max(jnp.abs(
        ops.cosine_block(rows, V) - ref.cosine_block_ref(rows, V))))
    print(f"  madc_block vs reference at n={n}: max |err| = {err_madc!r}")
    print(f"  cosine_block vs reference at ({rows.shape[0]}, {s.d_w}, "
          f"{V.shape[1]}): max |err| = {err_edc!r} (tolerance {KERNEL_TOL})")
    _check(err_madc <= KERNEL_TOL, f"madc_block error {err_madc!r}")
    _check(err_edc <= KERNEL_TOL, f"cosine_block error {err_edc!r}")
    return {"compiled": not ops.interpret_mode(M),
            "kernel_cold_start": n >= ops.madc_crossover_n(M)}


def _drift(tree, ref) -> tuple:
    """(||tree - ref|| / ||ref||, max |tree - ref|) over all leaves."""
    a, b = _leaves(tree), _leaves(ref)
    num = sum(float(np.sum((x.astype(np.float64) - y) ** 2))
              for x, y in zip(a, b))
    den = sum(float(np.sum(y.astype(np.float64) ** 2)) for y in b)
    return (float(np.sqrt(num / den)),
            max(float(np.max(np.abs(x - y))) for x, y in zip(a, b)))


def mesh_phase(s: Setting, data, model) -> dict:
    devs = jax.devices()
    cfg = s.cfg()
    with jax.default_device(devs[0]):
        one = FedGroupTrainer(model, data, cfg)
    one.mesh = None              # the plain-jit executor on the first chip
    runs = {"(4,) data mesh": FedGroupTrainer(model, data, cfg),
            "(2, 2) mesh": FedGroupTrainer(model, data, cfg,
                                           mesh=make_fed_mesh(2, 2))}
    _check(dict(runs["(4,) data mesh"].mesh.shape) == {"data": len(devs)},
           "the trainer did not pick the data mesh over every device")
    prev = None
    for r in range(3):
        with jax.default_device(devs[0]):
            acc1 = one.run(1).rounds[-1].weighted_acc
        if prev is not None:
            print(f"  round {r} 1 device: update ||d||/||p|| = "
                  f"{_drift(one.group_params, prev)[0]!r}")
        prev = _leaves(one.group_params)
        for tag, tr in runs.items():
            d_acc = abs(tr.run(1).rounds[-1].weighted_acc - acc1)
            rel, top = _drift(tr.group_params, one.group_params)
            same = bool(np.array_equal(tr.membership, one.membership))
            print(f"  round {r} {tag} vs 1 device: |acc diff| = {d_acc!r}, "
                  f"drift ||d||/||p|| = {rel!r} (max |d| = {top!r}), "
                  f"same membership: {same}", flush=True)
            _check(d_acc <= MESH_ACC_TOL,
                   f"round {r} {tag}: accuracy diff {d_acc!r}")
            _check(rel <= PARAM_RTOL, f"round {r} {tag}: drift {rel!r}")
    for tag, tr in (("1 device", one), *runs.items()):
        _rounds(tag, tr.history)
        _finite(tag, tr.group_params)
        tr.close()
    return {}


# ---------------------------------------------------------------------------
class _CompileClock:
    """Seconds JAX spent getting compiled programs (backend compiles and
    persistent-cache loads) and the persistent-cache hits, process-wide."""

    def __init__(self):
        self.seconds, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def _peak_bytes():
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    return max(peaks) if None not in peaks else "not reported"


def _run_phase(name: str, fn, clock: _CompileClock, cache_dir: str, *args):
    """One phase with its readings; -> its facts, or None if it failed."""
    print(f"== phase {name}", flush=True)
    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.hits
    try:
        facts = fn(*args)
    except Exception as e:                              # noqa: BLE001
        # a phase boundary: record the failure, run the remaining phases
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        print(f"== phase {name} FAILED: {type(e).__name__}: {e}", flush=True)
        return None
    print(f"== phase {name} passed: wall_s={time.perf_counter() - t0!r} "
          f"compile_s={clock.seconds - c0!r} "
          f"persistent_cache_hits={clock.hits - h0} "
          f"peak_device_bytes={_peak_bytes()} compile_cache={cache_dir}",
          flush=True)
    return facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="FedGroup smoke run at FEMNIST MLP-512 width on TPU")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs outside
    cache_dir = enable_compile_cache()
    clock = _CompileClock()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    print(f"# device {device}, compile cache {cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} on a host with "
              f"{device['count']} devices", file=sys.stderr)
        return 1

    s = Setting()
    t0 = time.perf_counter()
    data, model = s.data(), s.model()
    print(f"# data made in {time.perf_counter() - t0!r} s (set-up)")
    phases = ([("mesh", mesh_phase)] if args.chips == 4 else
              [("per-round", per_round_phase), ("block", block_phase),
               ("madc", madc_phase)])
    failed = []
    for name, fn in phases:
        facts = _run_phase(name, fn, clock, cache_dir, s, data, model)
        if facts is None:
            failed.append(name)
        elif name == "madc" and not (facts["compiled"]
                                     and facts["kernel_cold_start"]):
            print(f"== phase madc FAILED: the cold start did not run the "
                  f"compiled MADC kernel ({facts})")
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
