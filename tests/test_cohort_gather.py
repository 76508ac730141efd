"""The pinned cohort gather: K block copies from a client-major stack.

``fed.engine.gather_cohort`` (and the block / async executors' in-program
gather, ``fed.rounds.gather_clients``) must hand the round exactly the
rows ``x[sel]``, ``y[sel]``, ``n[sel]`` would, bit for bit, whatever the
cohort: a full one, one client, a dropout-shortened one, everyone. The
trainer pins its train stack packed client-major (``fed.rounds
.ClientStack``), and counts the rows it gathers in
``stage.rows_gathered``.

Block and per-round rounds stay bit-identical through the change: that is
``tests/test_round_block.py::TestBlockBitIdentity`` (all four frameworks),
and the async D=1 equivalence in ``tests/test_async.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.core.fedgroup import FedGroupTrainer
from repro.data.generators import mnist_like
from repro.fed import parallel as parallel_lib
from repro.fed import rounds as rounds_lib
from repro.fed.engine import FedAvgTrainer, FedConfig, gather_cohort
from repro.fed.rounds import pack_clients

N, S, D = 40, 7, 5


@pytest.fixture(scope="module")
def stack():
    """A pinned (x, y, n) stack whose floats include -0.0, infinities and a
    NaN with a payload, so a copy that is not bit-exact shows."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((N, S, D)).astype(np.float32)
    x[3, 0, 0], x[5, 1, 2], x[7, 2, 1] = -0.0, np.inf, -np.inf
    x[9, 3, 4] = np.uint32(0x7FC0BEEF).view(np.float32)
    y = rng.integers(0, 10, (N, S)).astype(np.int32)
    n = rng.integers(1, S + 1, N).astype(np.int32)
    return x, y, n


def _cohort(kind):
    rng = np.random.default_rng(1)
    if kind == "k20":
        return rng.choice(N, 20, replace=False)
    if kind == "k1":
        return np.array([N - 1])
    if kind == "dropout":               # 20 drawn, 7 stragglers dropped
        return rng.choice(N, 20, replace=False)[:13]
    return rng.permutation(N)           # "all": K = N


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


KINDS = ["k20", "k1", "dropout", "all"]


class TestGatherIsIndexing:
    @pytest.mark.parametrize("kind", KINDS)
    def test_gather_cohort_bit_exact(self, stack, kind):
        sel = _cohort(kind).astype(np.int32)
        got = gather_cohort(jax.tree_util.tree_map(jnp.asarray,
                                                   pack_clients(*stack)),
                            jnp.asarray(sel))
        for g, a in zip(got, stack):
            assert g.shape == (len(sel),) + a.shape[1:]
            np.testing.assert_array_equal(_bits(g), _bits(a[sel]))

    @pytest.mark.parametrize("data_slices", [1, 2, 3],
                             ids=["one_device", "sharded", "indivisible"])
    def test_in_program_gather_bit_exact(self, stack, data_slices):
        """The block executor's gather, inside a scan over staged cohorts,
        on the branch a mesh of ``data_slices`` picks (the index gather
        where it spreads the stack's client axis)."""
        mesh = AbstractMesh((data_slices,), ("data",))
        sharded = parallel_lib.shards_client_axis(mesh, N)
        assert sharded == (data_slices == 2)
        idx = np.stack([_cohort("k20")[:10], _cohort("dropout")[:10]])
        idx = idx.astype(np.int32)

        @jax.jit
        def scan(st, idx):
            return jax.lax.scan(
                lambda c, ix: (c, rounds_lib.gather_clients(
                    st, ix, sharded=sharded)), None, idx)[1]

        got = scan(jax.tree_util.tree_map(jnp.asarray,
                                          pack_clients(*stack)),
                   jnp.asarray(idx))
        for g, a in zip(got, stack):
            np.testing.assert_array_equal(_bits(g), _bits(a[idx]))


@pytest.fixture(scope="module")
def small_data():
    return mnist_like(seed=0, n_clients=24, classes_per_client=2,
                      total_train=1200, dim=16)


@pytest.fixture(scope="module")
def small_model():
    from repro.models.paper_models import mclr
    return mclr(16, 10)


def _cfg(**kw):
    base = dict(n_rounds=4, clients_per_round=6, local_epochs=1,
                batch_size=10, lr=0.05, n_groups=2, pretrain_scale=3, seed=0)
    base.update(kw)
    return FedConfig(**base)


class TestPack:
    @pytest.mark.parametrize("rows", [(7, 5), (8, 128), (3,), (320, 784)],
                             ids=["padded", "one_tile", "labels", "femnist"])
    def test_packed_blocks_are_whole_tiles(self, rows):
        a = np.arange(4 * np.prod(rows), dtype=np.float32).reshape(
            (4,) + rows)
        p = pack_clients(a, a[..., 0] if len(rows) > 1 else a,
                         np.ones(4, np.int32))
        assert p.x.shape[0] == 4 and p.x.shape[2] == 128
        assert p.x.shape[1] % 8 == 0       # whole (8, 128) tiles a client
        assert p.x_rows == rows
        flat = p.x.reshape(4, -1)
        np.testing.assert_array_equal(flat[:, :a[0].size],
                                      a.reshape(4, -1))
        assert not flat[:, a[0].size:].any()


class TestPinnedTrainer:
    def test_train_stack_is_packed_client_major(self, small_model,
                                                small_data):
        tr = FedAvgTrainer(small_model, small_data, _cfg())
        st = tr._train_stack
        N, S, D = small_data.x_train.shape
        assert st.x_rows == (S, D) and st.y_rows == (S,)
        assert st.x.shape[0] == N and st.x.shape[2] == 128
        assert st.x.format.layout.major_to_minor == (0, 1, 2)
        every = gather_cohort(st, jnp.arange(N))
        for got, want in zip(every, (small_data.x_train, small_data.y_train,
                                     small_data.n_train)):
            np.testing.assert_array_equal(np.asarray(got), want)

    @pytest.mark.parametrize("path", [{}, {"block_size": 2},
                                      {"async_depth": 1}],
                             ids=["per_round", "block", "async"])
    def test_rows_gathered_per_round(self, small_model, small_data, path):
        tr = FedAvgTrainer(small_model, small_data, _cfg(**path))
        rows = small_data.x_train.shape[1]
        K = tr.cfg.clients_per_round
        reg = tr.obs.registry
        assert reg.get("stage.rows_gathered") == 0
        tr.run(2)
        assert reg.get("stage.rows_gathered") == 2 * K * rows
        tr.run(2)
        assert reg.get("stage.rows_gathered") == 4 * K * rows

    def test_rows_gathered_counts_the_cold_start(self, small_model,
                                                 small_data):
        tr = FedGroupTrainer(small_model, small_data, _cfg())
        rows = small_data.x_train.shape[1]
        K = tr.cfg.clients_per_round
        reg = tr.obs.registry
        tr.round(0)
        after_first = reg.get("stage.rows_gathered")
        assert after_first > K * rows         # the cold start gathered too
        assert after_first % rows == 0
        tr.round(1)
        grown = reg.get("stage.rows_gathered") - after_first
        # a round gathers its cohort, plus any newcomers it cold-starts
        assert grown == (K + tr.last_cold) * rows
