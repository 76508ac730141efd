"""One run of one benchmark cell, driven by data.

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``), a
traffic mix (``bench/traffic/``) and its chips; its limits for ``correct``
are in ``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``. A configuration's model is its kind module
(``bench/models/<kind>.py``) and its data its generator
(``bench/data/<generator>.py``). Nothing here names a cell, a
configuration, a model or a metric.

A run:

1. set-up: makes the data from the seed, builds ``FedGroupTrainer`` (the
   system under test), runs the group cold start (Alg. 3), then drives
   the first ``check_rounds`` rounds through ``FedGroupTrainer.round`` —
   the window's own call, which selects its own cohorts — recording what
   each produced. These rounds also compile every program the window
   runs.
2. window: ``round(t)`` in a loop for ``--seconds``, each round timed from
   its call until its metrics are on the host. With ``--trace 1`` the
   window is shorter (the traffic's ``trace_seconds``) and profiled.
3. after the window: peak device memory is read, the trainer is freed,
   and the reference (``bench/reference.py``) follows the check rounds
   from the state they started from; ``bench/compare.py`` turns both into
   the numbers held against the cell's limits.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib
import json
import math
import os
import shutil
import tempfile
import time

import numpy as np

from bench import models

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX's persistent compilation cache, at a fixed path inside the checkout
# (a path that moved would never hit); listed in .gitignore. Where
# JAX_COMPILATION_CACHE_DIR is set, JAX keeps its cache there instead.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
ANNOTATIONS = ("round", "eval", "next_cohort", "cold_start")


class NoChip(RuntimeError):
    """JAX found no accelerator, or not as many chips as the cell needs."""


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, traffic, limits and metric names."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _json(os.path.join(root, "bench", "traffic",
                                 f"{cell['traffic']}.json"))
    lim = os.path.join(root, "bench", "limits", f"{workload}.json")
    limits = _json(lim)["limits"]

    def mine(metrics):
        return [m["name"] for m in metrics
                if workload in m.get("workloads", [workload])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


# ---------------------------------------------------------------------------
# JAX set-up
# ---------------------------------------------------------------------------
class CompileLog:
    """Backend compiles (with their seconds) and persistent-cache loads,
    stamped with the host clock, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        self.compiles, self.loads = [], []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), duration_secs))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.loads.append(time.perf_counter())

    def seconds_before(self, t: float) -> float:
        return sum(d for s, d in self.compiles if s < t)

    def count_between(self, a: float, b: float) -> int:
        return (sum(a <= s <= b for s, _ in self.compiles)
                + sum(a <= s <= b for s in self.loads))


def start_jax():
    """Point JAX at the checkout's compilation cache (unless
    ``JAX_COMPILATION_CACHE_DIR`` names one), cache every program however
    quick its compile, and keep the TPU runtime's logs off disk. Call
    before anything compiles."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return CompileLog()


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_chip and d.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d.platform!r})")
    if require_chip and len(devs) != chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX found "
                     f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    return None if None in peaks else int(max(peaks))


# ---------------------------------------------------------------------------
# the system under test and its feeding
# ---------------------------------------------------------------------------
def build_trainer(config: dict, traffic: dict, seed: int, data: dict):
    """FedGroupTrainer of the configuration's model at its FedGroup
    settings and the traffic's cohort size and eval cadence, over pinned
    client data or a streamed ``Population`` (``traffic["feeding"]``).
    The cold start pre-trains ``cold_start_share`` of the clients active
    at the start."""
    from repro.core.fedgroup import FedGroupTrainer
    from repro.data.federated import FederatedData
    from repro.fed.engine import FedConfig

    m, f = config["model"], config["fed"]
    if f["framework"] != "fedgroup":
        raise ValueError(f"unsupported configuration {config['name']!r}")
    kind = models.load(config)
    fd = pop = None
    if traffic["feeding"] == "pinned":
        fd = FederatedData(config["name"], data["x_train"], data["y_train"],
                           data["n_train"], data["x_test"], data["y_test"],
                           data["n_test"], data["n_classes"])
        active = len(data["n_train"])
    elif traffic["feeding"] == "population":
        from repro.fed.population import Population, PopulationConfig
        from repro.fed.store import VirtualClientStore
        store = VirtualClientStore(
            config["name"], len(data["n_train"]), data["client_fn"],
            max_train=data["max_train"], max_test=data["max_test"],
            feat=kind.row_shape(m, config["data"]),
            n_classes=data["n_classes"],
            n_train=data["n_train"], n_test=data["n_test"])
        pop = Population(store, PopulationConfig(
            initial_active=traffic["initial_active"],
            arrival_rate=traffic["arrival_rate"],
            newcomers_join=traffic["newcomers_join"],
            prefetch=traffic["prefetch"],
            eval_clients=traffic["eval_clients"]))
        active = traffic["initial_active"]
    else:
        raise ValueError(f"unsupported feeding {traffic['feeding']!r}")
    alpha = math.ceil(traffic["cold_start_share"] * active / f["n_groups"])
    cfg = FedConfig(seed=seed, clients_per_round=traffic["clients_per_round"],
                    local_epochs=f["local_epochs"],
                    batch_size=f["batch_size"], lr=f["lr"],
                    n_groups=f["n_groups"], pretrain_scale=alpha,
                    eta_g=f["eta_g"], measure=f["measure"],
                    eval_every=traffic["eval_every"])
    tr = FedGroupTrainer(kind.program(m, config["data"]), fd, cfg,
                         population=pop)
    if tr.model_size != m["d_w"]:
        raise ValueError(f"model has d_w={tr.model_size}, the configuration "
                         f"states {m['d_w']}")
    return tr


class Feed:
    """Drives ``round`` and records each round's cohort. The trainer
    selects its cohorts itself, as ``run()`` has it do; the benchmark wraps
    this trainer's ``_select`` to record each. Streamed, it also wraps
    ``Population.next_cohort`` and ``client_cold_start`` to time the host
    seconds each takes (``spans``), inside a trace annotation when
    ``annotate``."""

    def __init__(self, tr):
        self.tr = tr
        self.annotate = False
        self.spans = {"next_cohort": 0.0, "cold_start": 0.0}
        self.streamed = tr.population is not None
        self.last = None

        def select(orig=tr._select):
            self.last = np.asarray(orig())
            return self.last
        tr._select = select
        if self.streamed:
            pop = tr.population
            pop.next_cohort = lambda orig=pop.next_cohort: self._timed(
                "next_cohort", orig)
            tr.client_cold_start = lambda ids, orig=tr.client_cold_start: \
                self._timed("cold_start", orig, ids)

    def _timed(self, name, fn, *args):
        import jax
        a = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(name):
                out = fn(*args)
        else:
            out = fn(*args)
        self.spans[name] += time.perf_counter() - a
        return out

    def round(self, t: int):
        """One round -> (its RoundMetrics, its cohort)."""
        return self.tr.round(t), self.last


def _host(tree):
    import jax
    return jax.tree_util.tree_map(np.array, tree)


def state_of(tr) -> dict:
    """Host copy of the state a round starts from."""
    return {"groups": _host(tr.group_params), "glob": _host(tr.params),
            "group_dir": np.array(tr.group_delta),
            "membership": np.array(tr.membership), "key": np.array(tr.key)}


def eval_ids(tr) -> np.ndarray:
    """The clients the trainer's eval covers (before the assigned filter)."""
    if tr.population is not None:
        return np.asarray(tr.population.eval_ids())
    return np.arange(tr.n_clients)


def check_rounds(tr, feed: Feed, data: dict, n: int):
    """Drive the first ``n`` rounds through ``round`` and record what each
    produced. -> (start state, readings, cohorts). A reading's
    ``correct`` is None in a round the eval cadence skips."""
    start = state_of(tr)
    ids = eval_ids(tr)
    readings, cohorts = [], []
    for t in range(n):
        m, idx = feed.round(t)
        mem = np.array(tr.membership)
        n_test = int(data["n_test"][ids[mem[ids] >= 0]].sum())
        readings.append({
            "groups": _host(tr.group_params), "glob": _host(tr.params),
            "membership": mem, "loss": float(m.mean_loss),
            "correct": (None if math.isnan(m.weighted_acc)
                        else int(round(m.weighted_acc * n_test))),
            "n_test": n_test})
        cohorts.append(np.array(idx))
    return start, readings, cohorts


def reference_rounds(data, config: dict, start: dict, cohorts, prog: list,
                     ids, follow: bool = True, **kw) -> list:
    """The reference's readings over the same cohorts from ``start``; it
    evaluates in the rounds the program evaluated, over the same clients,
    and its newcomers join the groups the program chose (``follow``)."""
    from bench.reference import Reference
    import jax.numpy as jnp
    ref = Reference(data, config["fed"], models.load(config).apply, **kw)
    state = dict(start, key=jnp.asarray(start["key"]))
    out = []
    for idx, p in zip(cohorts, prog):
        state = ref.round(state, idx, None if p["correct"] is None else ids,
                          p["membership"] if follow else None)
        out.append(state)
    return out


def warm_streamed(tr, feed: Feed, traffic: dict):
    """Compile what a streamed round can need beyond the check rounds:
    the eq.-9 pre-training solve and its cosine match at every newcomer
    count a cohort can hold, and the streamed eval at every block size up
    to ``eval_warm_clients``. Read-only: no trainer state changes."""
    import jax
    import jax.numpy as jnp
    from repro.core import measures
    from repro.models.modules import flatten_updates

    live = feed.last                # the cohort on the device now
    for c in range(1, len(live) + 1):
        x, y, n = tr._client_batch(live[:c])
        deltas, _ = tr.pretrain_solver(tr.params, x, y, n,
                                       jax.random.split(tr.key, c))
        sim = measures.cosine_similarity_matrix(
            jax.vmap(flatten_updates)(deltas), tr.group_delta)
        np.asarray(jnp.argmin((-sim + 1.0) / 2.0, axis=1))
    ids = eval_ids(tr)
    for j in range(tr.m):           # one slice program per group index
        params = tr.group_param(j)
    for b in range(1, min(int(traffic["eval_warm_clients"]), len(ids)) + 1):
        for _, x, y, n in tr.population.eval_batches(ids[:b]):
            np.asarray(tr.eval_fn(params, x, y, n))


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Facts:
    """What the window measured, for the per-layer metric readers."""
    rounds: int
    evals: int
    window_s: float
    chips: int
    peaks: dict | None
    live_flops: int
    compile_s: float
    compiles_in_window: int
    spans: dict | None = None       # host seconds by span, streamed feeding
    trace: object = None


def _window(tr, feed: Feed, data: dict, t0: int, seconds: float,
            config: dict, annotate: bool):
    """Rounds from ``t0`` until ``seconds`` have passed."""
    import jax
    from bench import flops as flops_lib

    f = config["fed"]
    per_sample = models.load(config).train_flops_per_sample(
        config["model"], config["data"])
    lat, live, failed, evals = [], 0, 0, 0
    t = t0
    feed.annotate = annotate
    feed.spans = dict.fromkeys(feed.spans, 0.0)
    if annotate:
        orig = tr._round_eval

        def _round_eval(tt):
            with jax.profiler.TraceAnnotation("eval"):
                return orig(tt)
        tr._round_eval = _round_eval
    w0 = time.perf_counter()
    while True:
        a = time.perf_counter()
        if annotate:
            with jax.profiler.TraceAnnotation("round"):
                r, idx = feed.round(t)
        else:
            r, idx = feed.round(t)
        b = time.perf_counter()
        lat.append(b - a)
        live += flops_lib.live_sgd_flops(
            data["n_train"][idx], epochs=f["local_epochs"],
            batch_size=f["batch_size"], per_sample=per_sample)
        failed += not (math.isfinite(r.mean_loss)
                       and math.isfinite(r.discrepancy))
        evals += not math.isnan(r.weighted_acc)
        t += 1
        if b - w0 >= seconds:
            return w0, b, lat, live, failed, evals


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True, root: str = ROOT,
        parts: dict | None = None) -> tuple:
    """One run of a cell -> (result dict, check lines). ``parts``
    replaces the cell's files (tests)."""
    log = start_jax()
    parts = parts or load_cell(workload, root)
    cell, config, traffic = parts["cell"], parts["config"], parts["traffic"]
    device = device_info(cell["chips"], require_chip)

    import jax
    from bench import compare
    from bench import generators as gen
    from bench.peaks import device_peaks

    peaks = device_peaks(device["kind"]) if require_chip else None
    data = gen.make_data(seed, config, traffic)
    tr = build_trainer(config, traffic, seed, data)
    feed = Feed(tr)
    tr.group_cold_start()
    n_check = int(traffic["check_rounds"])
    start, prog, cohorts = check_rounds(tr, feed, data, n_check)
    ids = eval_ids(tr)
    if feed.streamed:
        warm_streamed(tr, feed, traffic)
    t_setup = time.perf_counter()
    setup_s = t_setup - t_start

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        # host annotations and device activity; no Python call tracing,
        # which costs the host more than the rounds it traces
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            w0, w1, lat, live, failed, evals = _window(
                tr, feed, data, n_check,
                min(seconds, traffic["trace_seconds"]), config, True)
        jax.profiler.stop_trace()
    else:
        w0, w1, lat, live, failed, evals = _window(
            tr, feed, data, n_check, seconds, config, False)
    window_s = w1 - w0
    device["memory_peak_bytes"] = memory_peak()
    tr.close()
    del tr
    gc.collect()

    rounds = len(lat)
    facts = Facts(rounds=rounds, evals=evals, window_s=window_s,
                  chips=device["count"], peaks=peaks, live_flops=live,
                  compile_s=log.seconds_before(t_setup),
                  compiles_in_window=log.count_between(w0, w1),
                  spans=feed.spans if feed.streamed else None)
    result = {"correct": False, "attempted": rounds, "failed": failed}

    if trace:
        from bench import trace_reduce
        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        facts.trace = trace_reduce.reduce(trace_reduce.load(files[0]),
                                          labels=ANNOTATIONS)
        shutil.rmtree(tdir, ignore_errors=True)
        metrics = {}
        for name in parts["per_layer"]:
            reader = importlib.import_module(f"bench.metrics.{name}")
            value = reader.read(facts)
            if value is not None:
                metrics[name] = {"value": value, "unit": reader.UNIT}
        device["busy_s"] = facts.trace.busy_s
        device["window_s"] = facts.trace.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in facts.trace.top_ops],
            "idle_gaps": [[n, s] for n, s in facts.trace.idle_gaps]}
    else:
        e2e = {"client_updates_per_s": (
                   rounds * traffic["clients_per_round"] / window_s,
                   "updates/s"),
               "round_ms_p95": (float(np.percentile(lat, 95)) * 1e3, "ms"),
               "setup_s": (setup_s, "s")}
        metrics = {k: {"value": e2e[k][0], "unit": e2e[k][1]}
                   for k in parts["end_to_end"]}
    result["metrics"] = metrics
    result["device"] = device

    ref = reference_rounds(data, config, start, cohorts, prog, ids)
    nums = compare.numbers(start, prog, ref)
    ok, checks = compare.verdict(nums, parts["limits"])
    result["correct"] = ok and failed == 0
    result["checks"] = checks
    lines = [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
             for k, c in checks.items()]
    return result, lines
