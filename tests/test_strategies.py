"""Assignment-strategy zoo (fed/strategies.py): the FedClust partial-weight
cosine and LCFL hysteresis strategies against their serial host oracles,
plus registry-generic invariance properties every registered ``assign_fn``
must satisfy (permutation equivariance over clients, group-relabel
invariance) and trainer-level dispatch/population smoke tests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.fed import client as client_lib
from repro.fed import rounds, server as server_lib, strategies
from repro.fed.engine import FedConfig
from repro.fed.fesem import fesem_state_update
from repro.models.modules import flatten_updates
from repro.models.paper_models import mclr
from test_dynamic_assignment import _assert_groups_close, _setup


def _local_flat_near(gp_list, K, jitter=1e-3):
    """Per-client flattened local models near group (i % m)'s center."""
    m = len(gp_list)
    centers = np.stack([np.asarray(flatten_updates(p)) for p in gp_list])
    return np.stack([centers[i % m] + jitter for i in range(K)])


def _d_w(params):
    return int(np.asarray(flatten_updates(params)).shape[0])


# ---------------------------------------------------------------------------
# FedClust fused round vs the serial oracle
# ---------------------------------------------------------------------------
class TestFusedFedClust:
    def _run_both(self, model, gp_list, local_flat, X, Y, n, keys, *,
                  frac=0.5, epochs=2, batch=5):
        m, max_n = len(gp_list), X.shape[1]
        K = X.shape[0]
        d_head = strategies.fedclust_head_dim(local_flat.shape[1], frac)
        fused = jax.jit(rounds.make_round_executor(
            model, epochs=epochs, batch_size=batch, lr=0.05, mu=0.0,
            n_groups=m, max_samples=max_n,
            assign_fn=strategies.make_fedclust_assign(d_head),
            state_update_fn=fesem_state_update))
        state = {"local_flat": jnp.asarray(local_flat),
                 "idx": jnp.arange(K, dtype=jnp.int32)}
        out = fused(rounds.stack_trees(gp_list), state, X, Y, n, keys)
        solver = client_lib.make_batch_solver(
            model, epochs=epochs, batch_size=batch, lr=0.05, mu=0.0,
            max_samples=max_n)
        ref = strategies.serial_fedclust_round(
            solver, gp_list, local_flat, X, Y, n, keys, d_head=d_head)
        return out, ref

    def test_matches_serial_oracle(self):
        model, gp_list, X, Y, n, keys = _setup()
        lf = _local_flat_near(gp_list, X.shape[0])
        out, (ref_groups, ref_mem, ref_local, ref_disc) = self._run_both(
            model, gp_list, lf, X, Y, n, keys)
        assert np.array_equal(np.asarray(out.membership), ref_mem)
        assert len(np.unique(ref_mem)) == 3
        _assert_groups_close(out.group_params, ref_groups)
        np.testing.assert_allclose(
            np.asarray(out.assign_state["local_flat"]), ref_local, atol=1e-5)
        assert float(out.discrepancy) == pytest.approx(ref_disc, abs=1e-4)

    @pytest.mark.parametrize("m", [1, 3, 5])
    def test_assignment_oracle_bit_identical(self, m):
        """In-program trailing-head cosine argmax == the numpy oracle,
        exactly, for every cluster count of the paper's sweep."""
        model, gp_list, X, Y, n, keys = _setup(m=m, K=15)
        lf = _local_flat_near(gp_list, 15, jitter=5e-3)
        d_head = strategies.fedclust_head_dim(lf.shape[1], 0.25)
        assign = strategies.make_fedclust_assign(d_head)
        state = {"local_flat": jnp.asarray(lf),
                 "idx": jnp.arange(15, dtype=jnp.int32)}
        got = np.asarray(jax.jit(assign)(
            rounds.stack_trees(gp_list), X, Y, n, state))
        centers = np.stack([np.asarray(flatten_updates(p))
                            for p in gp_list])
        ref = strategies.serial_fedclust_assign(centers, lf, d_head)
        assert np.array_equal(got, ref)

    def test_head_dim_bounds(self):
        assert strategies.fedclust_head_dim(100, 0.25) == 25
        assert strategies.fedclust_head_dim(100, 0.0) == 1   # floor
        assert strategies.fedclust_head_dim(100, 2.0) == 100  # cap
        assert strategies.fedclust_head_dim(1, 0.5) == 1


# ---------------------------------------------------------------------------
# LCFL fused round vs the serial oracle
# ---------------------------------------------------------------------------
class TestFusedLCFL:
    def _run_both(self, model, gp_list, cur, X, Y, n, keys, *,
                  margin=0.1, epochs=2, batch=5):
        m, max_n = len(gp_list), X.shape[1]
        fused = jax.jit(rounds.make_round_executor(
            model, epochs=epochs, batch_size=batch, lr=0.05, mu=0.0,
            n_groups=m, max_samples=max_n,
            assign_fn=strategies.make_lcfl_assign(model, margin)))
        out = fused(rounds.stack_trees(gp_list),
                    jnp.asarray(cur, jnp.int32), X, Y, n, keys)
        solver = client_lib.make_batch_solver(
            model, epochs=epochs, batch_size=batch, lr=0.05, mu=0.0,
            max_samples=max_n)
        loss_fn = client_lib.make_loss_eval_fn(model)
        ref = strategies.serial_lcfl_round(
            solver, loss_fn, gp_list, cur, X, Y, n, keys, margin=margin)
        return out, ref

    def test_matches_serial_oracle_cold(self):
        """All-cold cohort (cur = -1): LCFL degenerates to IFCA argmin."""
        model, gp_list, X, Y, n, keys = _setup()
        cur = np.full(X.shape[0], -1, np.int64)
        out, (ref_groups, ref_mem, ref_disc) = self._run_both(
            model, gp_list, cur, X, Y, n, keys)
        assert np.array_equal(np.asarray(out.membership), ref_mem)
        assert len(np.unique(ref_mem)) == 3
        _assert_groups_close(out.group_params, ref_groups)
        assert float(out.discrepancy) == pytest.approx(ref_disc, abs=1e-4)

    def test_matches_serial_oracle_warm(self):
        """Warm cohort with deliberately wrong current groups and a huge
        margin: hysteresis must keep every valid current assignment."""
        model, gp_list, X, Y, n, keys = _setup()
        K = X.shape[0]
        cur = (np.arange(K) + 1) % 3    # shifted off the loss-optimal group
        out, (ref_groups, ref_mem, _) = self._run_both(
            model, gp_list, cur, X, Y, n, keys, margin=1e6)
        assert np.array_equal(np.asarray(out.membership), ref_mem)
        assert np.array_equal(ref_mem, cur)     # nobody moved
        _assert_groups_close(out.group_params, ref_groups)

    @pytest.mark.parametrize("m", [1, 3, 5])
    def test_assignment_oracle_bit_identical(self, m):
        model, gp_list, X, Y, n, keys = _setup(m=m, K=15)
        K = X.shape[0]
        rng = np.random.default_rng(1)
        cur = rng.integers(-1, m, K)            # mix of cold and warm
        assign = strategies.make_lcfl_assign(model, 0.1)
        got = np.asarray(jax.jit(assign)(
            rounds.stack_trees(gp_list), X, Y, n,
            jnp.asarray(cur, jnp.int32)))
        loss_fn = client_lib.make_loss_eval_fn(model)
        losses = np.stack([np.asarray(loss_fn(p, X, Y, n))
                           for p in gp_list])
        ref = strategies.serial_lcfl_assign(losses, cur, 0.1)
        assert np.array_equal(got, ref)

    def test_margin_zero_matches_ifca(self):
        """margin=0 keeps the incumbent only on exact loss ties — i.e. the
        decision is the plain argmin wherever the argmin is unique."""
        model, gp_list, X, Y, n, keys = _setup()
        K = X.shape[0]
        losses = np.stack([np.asarray(
            client_lib.make_loss_eval_fn(model)(p, X, Y, n))
            for p in gp_list])
        cur = (losses.argmin(0) + 1) % 3        # incumbent is never optimal
        ref = strategies.serial_lcfl_assign(losses, cur, 0.0)
        assert np.array_equal(ref, losses.argmin(0))


# ---------------------------------------------------------------------------
# Registry-generic invariance properties
# ---------------------------------------------------------------------------
_DYNAMIC = [name for name in strategies.available_strategies()
            if strategies.get_strategy(name).state_kind != "static"]


def _build_state(kind, gp_list, K, rng):
    if kind == "none":
        return None
    if kind == "membership":
        return jnp.asarray(rng.integers(-1, len(gp_list), K), jnp.int32)
    if kind == "local_flat":
        return {"local_flat": jnp.asarray(
                    _local_flat_near(gp_list, K, jitter=5e-3)),
                "idx": jnp.arange(K, dtype=jnp.int32)}
    raise AssertionError(kind)


def _permute_state(kind, state, perm):
    """The state as the permuted cohort would carry it."""
    if kind == "none":
        return None
    if kind == "membership":
        return state[perm]
    # local_flat: the table is global (N rows); only idx follows the cohort
    return {"local_flat": state["local_flat"], "idx": state["idx"][perm]}


def _relabel_state(kind, state, inv):
    """The state after groups are relabeled by g -> inv[g]."""
    if kind != "membership":
        return state
    cold = state < 0
    return jnp.where(cold, state, jnp.asarray(inv, jnp.int32)[
        jnp.clip(state, 0, len(inv) - 1)])


_PROP_CACHE = {}


def _prop_fixture(name):
    """Per-strategy compiled assign + a fixed problem, built once — the
    hypothesis examples only vary the permutation seed."""
    if name not in _PROP_CACHE:
        spec = strategies.get_strategy(name)
        model, gp_list, X, Y, n, keys = _setup(K=10)
        cfg = FedConfig(n_groups=len(gp_list))
        assign = jax.jit(spec.make_assign(model, _d_w(gp_list[0]), cfg))
        state = _build_state(spec.state_kind, gp_list, X.shape[0],
                             np.random.default_rng(0))
        base = np.asarray(assign(rounds.stack_trees(gp_list), X, Y, n,
                                 state))
        _PROP_CACHE[name] = (spec, gp_list, X, Y, n, assign, state, base)
    return _PROP_CACHE[name]


class TestStrategyProperties:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1))
    def test_permutation_equivariant_over_clients(self, perm_seed):
        """Reordering the cohort reorders the assignment and nothing else:
        assign(perm(clients)) == assign(clients)[perm], for every
        registered dynamic strategy."""
        for name in _DYNAMIC:
            spec, gp_list, X, Y, n, assign, state, base = _prop_fixture(name)
            perm = np.random.default_rng(perm_seed).permutation(X.shape[0])
            got = np.asarray(assign(
                rounds.stack_trees(gp_list), X[perm], Y[perm], n[perm],
                _permute_state(spec.state_kind, state, perm)))
            assert np.array_equal(got, base[perm]), name

    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1))
    def test_group_relabel_invariant(self, perm_seed):
        """Renaming the groups renames the assignment: with centers
        reordered by ``perm`` (and any group ids in the state relabeled to
        match), every client lands in the *same* group under its new id."""
        for name in _DYNAMIC:
            spec, gp_list, X, Y, n, assign, state, base = _prop_fixture(name)
            perm = np.random.default_rng(perm_seed).permutation(len(gp_list))
            inv = np.empty_like(perm)
            inv[perm] = np.arange(len(perm))
            got = np.asarray(assign(
                rounds.stack_trees([gp_list[j] for j in perm]), X, Y, n,
                _relabel_state(spec.state_kind, state, inv)))
            assert np.array_equal(got, inv[base]), name


# ---------------------------------------------------------------------------
# Registry API + trainers
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_all_builtin_registered(self):
        assert strategies.available_strategies() == \
            ["fedclust", "fesem", "ifca", "lcfl", "static"]

    def test_unknown_strategy_lists_available(self):
        with pytest.raises(KeyError, match="fedclust"):
            strategies.get_strategy("nope")

    def test_duplicate_registration_rejected(self):
        spec = strategies.get_strategy("ifca")
        with pytest.raises(ValueError, match="already registered"):
            strategies.register(spec)

    def test_bad_state_kind_rejected(self):
        with pytest.raises(ValueError, match="state_kind"):
            strategies.register(strategies.StrategySpec(
                "broken", object, "weird", None, ""))

    def test_make_trainer(self, tiny_model, tiny_fed_data, fast_cfg):
        tr = strategies.make_trainer("fedclust", tiny_model, tiny_fed_data,
                                     fast_cfg)
        assert tr.framework == "fedclust"


class TestStrategyTrainers:
    @pytest.mark.parametrize("name", ["fedclust", "lcfl"])
    def test_round_is_one_executor_dispatch(self, name, tiny_model,
                                            tiny_fed_data, fast_cfg):
        """The new strategies share the fused round: one dispatch, no
        separate estimation launch."""
        tr = strategies.make_trainer(name, tiny_model, tiny_fed_data,
                                     fast_cfg)
        calls = []
        real = tr._round_executor()

        def spy(*args, **kw):
            calls.append(1)
            return real(*args, **kw)

        tr._round_exec = spy
        tr.round(0)
        assert len(calls) == 1
        assert np.any(tr.membership >= 0)

    @pytest.mark.parametrize("name", ["fedclust", "lcfl"])
    def test_run_improves_and_counts_migrations(self, name, tiny_model,
                                                tiny_fed_data, fast_cfg):
        tr = strategies.make_trainer(name, tiny_model, tiny_fed_data,
                                     fast_cfg)
        hist = tr.run()
        assert hist.max_acc > 0.15
        assert int(tr.obs.registry.get("rounds.migrations")) >= 0

    def test_lcfl_hysteresis_reduces_churn(self, tiny_model, tiny_fed_data):
        """Sticky LCFL must migrate at most as much as margin=0 LCFL on the
        same seed/schedule (the whole point of the hysteresis rule)."""
        flips = {}
        for margin in (0.0, 10.0):
            cfg = FedConfig(n_rounds=6, clients_per_round=10, local_epochs=2,
                            batch_size=10, lr=0.05, n_groups=3, seed=0,
                            lcfl_margin=margin)
            tr = strategies.make_trainer("lcfl", tiny_model, tiny_fed_data,
                                         cfg)
            tr.run()
            flips[margin] = int(tr.obs.registry.get("rounds.migrations"))
        assert flips[10.0] <= flips[0.0]

    @pytest.mark.parametrize("name", ["fedclust", "lcfl"])
    def test_population_streamed_matches_pinned(self, name, tiny_model,
                                                tiny_fed_data, fast_cfg):
        """Same-seed streamed population == the pinned trainer,
        bit-identical final groups (the strategies thread their state
        through the cohort paths correctly)."""
        from repro.fed.population import Population, PopulationConfig
        from repro.fed.store import ArrayClientStore
        pinned = strategies.make_trainer(name, tiny_model, tiny_fed_data,
                                         fast_cfg)
        pinned.run()
        pop = Population(ArrayClientStore(tiny_fed_data),
                         PopulationConfig(prefetch=2))
        streamed = strategies.make_trainer(
            name, tiny_model, None, fast_cfg, population=pop)
        streamed.run()
        streamed.close()
        for a, b in zip(
                jax.tree_util.tree_leaves(pinned.group_params),
                jax.tree_util.tree_leaves(streamed.group_params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(pinned.membership,
                                      streamed.membership)
