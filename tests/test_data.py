"""Data pipeline: generators, partitioners, padding containers."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import generators as gen
from repro.data.federated import power_law_sizes


class TestMnistLike:
    def test_shapes_and_ranges(self):
        d = gen.mnist_like(seed=0, n_clients=50, classes_per_client=2,
                           total_train=3000, dim=64)
        assert d.n_clients == 50
        assert d.x_train.shape[0] == 50 and d.x_train.shape[2] == 64
        assert d.y_train.max() < 10 and d.y_train.min() >= 0
        assert np.all(d.n_train > 0)

    def test_label_skew(self):
        d = gen.mnist_like(seed=0, n_clients=40, classes_per_client=2,
                           total_train=3000, dim=32)
        for i in range(d.n_clients):
            c = d.client(i)
            classes = np.unique(np.concatenate([c["y"], c["y_test"]]))
            assert len(classes) <= 2

    def test_iid_when_all_classes(self):
        d = gen.mnist_like(seed=0, n_clients=20, classes_per_client=10,
                           total_train=4000, dim=32)
        more_than_5 = sum(len(np.unique(d.client(i)["y"])) > 5
                          for i in range(20))
        assert more_than_5 > 10

    def test_deterministic(self):
        a = gen.mnist_like(seed=3, n_clients=10, total_train=500, dim=16)
        b = gen.mnist_like(seed=3, n_clients=10, total_train=500, dim=16)
        np.testing.assert_array_equal(a.x_train, b.x_train)
        c = gen.mnist_like(seed=4, n_clients=10, total_train=500, dim=16)
        assert not np.array_equal(a.x_train, c.x_train)


class TestSynthetic:
    def test_paper_dims(self):
        d = gen.synthetic(1.0, 1.0, seed=0, n_clients=30)
        assert d.x_train.shape[2] == 60 and d.n_classes == 10

    def test_alpha_increases_heterogeneity(self):
        """Larger alpha -> client optima differ more -> labels differ more
        across clients for the same x region (proxy: per-client label hists)."""
        lo = gen.synthetic(0.0, 0.0, seed=0, n_clients=30)
        hi = gen.synthetic(2.0, 2.0, seed=0, n_clients=30)

        def hist_spread(d):
            hists = []
            for i in range(d.n_clients):
                y = d.client(i)["y"]
                h = np.bincount(y, minlength=10) / max(len(y), 1)
                hists.append(h)
            return np.std(np.stack(hists), axis=0).mean()
        assert hist_spread(hi) > hist_spread(lo)


class TestSent140Like:
    def test_shapes(self):
        d = gen.sent140_like(seed=0, n_clients=30, total_train=2000)
        assert d.n_classes == 2
        assert d.x_train.shape[2] == 25
        assert set(np.unique(d.y_train)) <= {0, 1}

    def test_lexicon_signal_exists(self):
        """A linear probe on token counts should beat chance, i.e. the
        synthetic sentiment labels are learnable."""
        d = gen.sent140_like(seed=0, n_clients=50, total_train=4000, vocab=200)
        X, Y = [], []
        for i in range(d.n_clients):
            c = d.client(i)
            for x, y in zip(c["x"], c["y"]):
                bow = np.bincount(x.astype(int), minlength=200)
                X.append(bow)
                Y.append(y)
        X, Y = np.stack(X).astype(float), np.asarray(Y)
        X -= X.mean(0)
        w = np.linalg.lstsq(X.T @ X + 10 * np.eye(200), X.T @ (Y * 2 - 1),
                            rcond=None)[0]
        acc = (((X @ w) > 0) == Y).mean()
        assert acc > 0.7


class TestFemnistLike:
    def test_writer_styles(self):
        d = gen.femnist_like(seed=0, n_clients=40, total_train=3000, dim=64,
                             n_styles=3)
        assert "style_of" in d.meta
        assert d.n_classes == 62


class TestPowerLaw:
    @given(st.integers(0, 500))
    @settings(max_examples=10, deadline=None)
    def test_bounds(self, seed):
        rng = np.random.default_rng(seed)
        s = power_law_sizes(rng, 100, 10000, min_size=10, max_size=512)
        assert s.min() >= 10 and s.max() <= 512 and len(s) == 100

    def test_skewed(self):
        rng = np.random.default_rng(0)
        s = power_law_sizes(rng, 1000, 100000)
        assert np.median(s) < s.mean()   # heavy right tail
