"""2-D (data × model) mesh + host-sharded client store (PR 4 tentpole).

Two layers of coverage:

  * in-process (1 device): ``ShardedClientStore`` gather decomposition /
    round-trip against the inner store, per-shard cohort slices, the
    federated-round PartitionSpecs, and the async per-shard state scatter
    (drain-before-gather determinism).
  * subprocess (forced host devices, pattern of tests/test_fed_parallel.py):
    a 2×2 ``(data, model)`` mesh run of FedAvg and FedGroup must reproduce
    the 1-device pinned run — same metrics trajectory, same final params
    (allclose: model-axis contractions reorder float reductions), same
    membership (exact) — and a streamed run over ``ShardedClientStore`` +
    per-shard prefetch must be *bit-identical* to the pinned 2×2 run
    (same compiled program, only the feeding differs; docs/scaling.md).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.data.generators import mnist_like
from repro.fed.population import Population, PopulationConfig
from repro.fed.store import (ArrayClientStore, ShardedClientStore,
                             shard_cohort_slices)
from repro.sharding.specs import cohort_pspec, group_param_pspec


@pytest.fixture(scope="module")
def small_data():
    return mnist_like(seed=0, n_clients=16, classes_per_client=2,
                      total_train=1200, dim=16)


class TestShardCohortSlices:
    def test_contiguous_equal_blocks(self):
        assert shard_cohort_slices(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]
        assert shard_cohort_slices(6, 1) == [(0, 6)]

    def test_non_divisible_returns_none(self):
        assert shard_cohort_slices(7, 4) is None
        assert shard_cohort_slices(4, 0) is None


class TestShardedStore:
    def test_gather_round_trips_inner_store(self, small_data):
        inner = ArrayClientStore(small_data)
        sharded = ShardedClientStore(inner, n_shards=4)
        idx = np.array([3, 11, 0, 7, 9, 1, 15, 2])
        for split in ("gather_train", "gather_test"):
            for a, b in zip(getattr(sharded, split)(idx),
                            getattr(inner, split)(idx)):
                np.testing.assert_array_equal(a, b)

    def test_shard_gathers_cover_cohort_slices(self, small_data):
        inner = ArrayClientStore(small_data)
        sharded = ShardedClientStore(inner, n_shards=2)
        idx = np.array([5, 2, 9, 14])
        parts = sharded.gather_train_shards(idx)
        assert len(parts) == 2
        x_full, y_full, n_full = inner.gather_train(idx)
        for s, (lo, hi) in enumerate(shard_cohort_slices(4, 2)):
            np.testing.assert_array_equal(parts[s][0], x_full[lo:hi])
            np.testing.assert_array_equal(parts[s][1], y_full[lo:hi])
            np.testing.assert_array_equal(parts[s][2], n_full[lo:hi])

    def test_non_divisible_cohort_falls_back(self, small_data):
        sharded = ShardedClientStore(ArrayClientStore(small_data), 4)
        idx = np.array([1, 2, 3])                 # 3 % 4 != 0
        assert sharded.gather_train_shards(idx) is None
        x, _, n = sharded.gather_train(idx)       # still serves the cohort
        np.testing.assert_array_equal(x, small_data.x_train[idx])
        np.testing.assert_array_equal(n, small_data.n_train[idx])

    def test_metadata_mirrors_inner(self, small_data):
        inner = ArrayClientStore(small_data)
        sharded = ShardedClientStore(inner, 2)
        assert sharded.n_clients == inner.n_clients
        assert sharded.max_train == inner.max_train
        np.testing.assert_array_equal(sharded.n_train, inner.n_train)
        with pytest.raises(ValueError):
            ShardedClientStore(inner, 0)

    def test_streamed_cohorts_match_array_store(self, small_data):
        """Same seed -> the sharded store's prefetched cohort stream is
        identical to the ArrayClientStore's (scheduler rng is shared)."""
        from repro.fed.engine import FedConfig
        cfg = FedConfig(clients_per_round=8, seed=0)
        cohorts = []
        for store in (ArrayClientStore(small_data),
                      ShardedClientStore(ArrayClientStore(small_data), 2)):
            pop = Population(store, PopulationConfig(prefetch=2))
            pop.attach(cfg)
            cohorts.append([pop.next_cohort() for _ in range(3)])
            pop.close()
        for ca, cs in zip(*cohorts):
            np.testing.assert_array_equal(ca.idx, cs.idx)
            np.testing.assert_array_equal(np.asarray(ca.x), np.asarray(cs.x))
            np.testing.assert_array_equal(np.asarray(ca.n), np.asarray(cs.n))


class TestAsyncStateScatter:
    def test_scatter_then_gather_is_ordered(self, small_data):
        """Per-shard async writes are drained before any gather — a
        reader can never observe a stale row."""
        from repro.fed.engine import FedConfig
        pop = Population(ShardedClientStore(ArrayClientStore(small_data), 2),
                         PopulationConfig())
        pop.attach(FedConfig(clients_per_round=8, seed=0))
        pop.state.init_local_flat(np.zeros(4, np.float32))
        idx = np.arange(8)
        for step in range(1, 4):                 # FIFO across rounds
            pop.scatter_local_flat(idx, np.full((8, 4), float(step)))
        rows = pop.gather_local_flat(idx)
        np.testing.assert_array_equal(rows, np.full((8, 4), 3.0))
        pop.close()

    def test_writer_error_surfaces_on_drain(self, small_data):
        from repro.fed.engine import FedConfig
        pop = Population(ArrayClientStore(small_data), PopulationConfig())
        pop.attach(FedConfig(clients_per_round=8, seed=0))
        pop._writer.submit(lambda: (_ for _ in ()).throw(OSError("disk")))
        with pytest.raises(RuntimeError, match="state-table write failed"):
            pop.gather_local_flat(np.arange(2))
        pop.close()


class TestFedRoundSpecs:
    def test_cohort_pspec_shards_client_axis_only(self):
        spec = cohort_pspec(3, data_axes=("data",))
        assert tuple(spec) == ("data", None, None)
        spec = cohort_pspec(2, data_axes=("pod", "data"))
        assert tuple(spec) == (("pod", "data"), None)

    def test_group_param_pspec_picks_largest_divisible_dim(self):
        # (m, d, C): d=16 divides 2, C=10 does not -> shard d over "model"
        assert tuple(group_param_pspec((3, 16, 10), 2)) == \
            (None, "model", None)
        # nothing divisible, or model axis 1 -> fully replicated
        assert tuple(group_param_pspec((3, 7, 9), 2)) == (None, None, None)
        assert tuple(group_param_pspec((3, 16, 10), 1)) == (None, None, None)
        # 1-D leaves (biases stacked over m) stay replicated
        assert tuple(group_param_pspec((3,), 2)) == (None,)


_DRIVER = r"""
import json, sys
import jax
import numpy as np
from repro.core.fedgroup import FedGroupTrainer
from repro.data.generators import mnist_like
from repro.fed.engine import FedAvgTrainer, FedConfig
from repro.models.paper_models import mclr

mode = sys.argv[1]                      # "1dev" | "2x2"
data = mnist_like(seed=0, n_clients=16, classes_per_client=2,
                  total_train=1200, dim=16)
model = mclr(16, 10)
cfg = FedConfig(n_rounds=3, clients_per_round=8, local_epochs=3,
                batch_size=10, lr=0.05, n_groups=2, pretrain_scale=3, seed=0)
mesh = None
if mode == "2x2":
    from repro.launch.mesh import make_fed_mesh
    mesh = make_fed_mesh(2, 2)
out = {"devices": jax.device_count()}
for cls in (FedAvgTrainer, FedGroupTrainer):
    tr = cls(model, data, cfg, mesh=mesh)
    h = tr.run(cfg.n_rounds)
    fw = cls.framework
    out[fw] = [[r.weighted_acc, r.mean_loss, r.discrepancy]
               for r in h.rounds]
    params = tr.group_params if fw == "fedgroup" else tr.params
    out[fw + "_params"] = {k: np.asarray(v).tolist()
                           for k, v in params.items()}
    if fw == "fedgroup":
        out["membership"] = tr.membership.tolist()
if mode == "2x2":
    # streamed over ShardedClientStore + per-shard prefetch must be
    # BIT-identical to the pinned 2x2 run just recorded in out["fedavg"]
    from repro.fed.population import Population, PopulationConfig
    from repro.fed.store import ArrayClientStore, ShardedClientStore
    pop = Population(ShardedClientStore(ArrayClientStore(data), 2),
                     PopulationConfig())
    st = FedAvgTrainer(model, None, cfg, mesh=mesh, population=pop)
    hs = st.run(cfg.n_rounds)
    st.close()
    stream = [[r.weighted_acc, r.mean_loss, r.discrepancy]
              for r in hs.rounds]
    out["stream_bit_identical"] = stream == out["fedavg"] and all(
        np.array_equal(np.asarray(st.params[k]),
                       np.asarray(out["fedavg_params"][k]))
        for k in st.params)
print(json.dumps(out))
"""


def _run_driver(n_devices: int, mode: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _DRIVER, mode], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


class TestMesh2DEquivalence:
    def test_2x2_mesh_matches_single_device(self):
        """A 2×2 (data, model) mesh reproduces the 1-device pinned run for
        FedAvg and FedGroup: metrics + params within reduction-order
        tolerance, membership exactly; and the sharded-store streamed run
        is bit-identical to the pinned run on the same mesh."""
        one = _run_driver(1, "1dev")
        two = _run_driver(4, "2x2")
        assert one["devices"] == 1 and two["devices"] == 4
        for fw in ("fedavg", "fedgroup"):
            np.testing.assert_allclose(
                np.asarray(one[fw]), np.asarray(two[fw]), atol=2e-3,
                err_msg=f"{fw} metrics diverged under the 2-D mesh")
            for k in one[fw + "_params"]:
                np.testing.assert_allclose(
                    np.asarray(one[fw + "_params"][k]),
                    np.asarray(two[fw + "_params"][k]), atol=2e-3,
                    err_msg=f"{fw} params[{k}] diverged under the 2-D mesh")
        assert one["membership"] == two["membership"]
        assert two["stream_bit_identical"]
