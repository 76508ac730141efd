"""Unit + property tests for the paper's measures (eq. 5-9)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import measures
from repro.core.svd import randomized_truncated_svd, truncated_svd_values


def _rand(key, n, d):
    return jax.random.normal(jax.random.PRNGKey(key), (n, d))


class TestCosineMatrix:
    def test_matches_numpy(self):
        dW = np.asarray(_rand(0, 12, 50))
        M = np.asarray(measures.cosine_similarity_matrix(jnp.asarray(dW)))
        nrm = dW / np.linalg.norm(dW, axis=1, keepdims=True)
        np.testing.assert_allclose(M, np.clip(nrm @ nrm.T, -1, 1), atol=1e-5)

    def test_diag_ones(self):
        M = measures.cosine_similarity_matrix(_rand(1, 8, 30))
        np.testing.assert_allclose(np.diag(np.asarray(M)), 1.0, atol=1e-5)

    @given(st.integers(3, 16), st.integers(4, 40), st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None)
    def test_bounded_symmetric(self, n, d, seed):
        M = np.asarray(measures.cosine_similarity_matrix(_rand(seed, n, d)))
        assert np.all(M <= 1.0 + 1e-5) and np.all(M >= -1.0 - 1e-5)
        np.testing.assert_allclose(M, M.T, atol=1e-5)


class TestMADC:
    def test_symmetric_zero_diag(self):
        M = measures.cosine_similarity_matrix(_rand(2, 10, 64))
        D = np.asarray(measures.madc(M))
        np.testing.assert_allclose(D, D.T, atol=1e-6)
        np.testing.assert_allclose(np.diag(D), 0.0, atol=1e-6)
        assert np.all(D >= -1e-6)

    def test_separates_clusters(self):
        """Two groups of identical directions: MADC within << across."""
        key = jax.random.PRNGKey(3)
        a = jax.random.normal(key, (1, 40))
        b = jax.random.normal(jax.random.fold_in(key, 1), (1, 40))
        dW = jnp.concatenate([jnp.tile(a, (5, 1)), jnp.tile(b, (5, 1))])
        dW = dW + 0.01 * jax.random.normal(jax.random.fold_in(key, 2), dW.shape)
        D = np.asarray(measures.madc(measures.cosine_similarity_matrix(dW)))
        within = (D[:5, :5].sum() + D[5:, 5:].sum()) / (2 * 5 * 4)
        across = D[:5, 5:].mean()
        assert across > 5 * within


class TestEDC:
    def test_metric_properties(self):
        """EDC is a true metric (Euclidean on embeddings): triangle ineq."""
        dW = _rand(4, 9, 100)
        D = np.asarray(measures.edc(dW, m=3))
        np.testing.assert_allclose(D, D.T, atol=1e-5)
        np.testing.assert_allclose(np.diag(D), 0.0, atol=1e-5)
        n = D.shape[0]
        for i in range(n):
            for j in range(n):
                for k in range(n):
                    assert D[i, j] <= D[i, k] + D[k, j] + 1e-5

    def test_approximates_madc_linearly(self):
        """Paper Fig. 5: the MADC -> EDC map is approximately linear.
        Check rank correlation > 0.75 on clustered data."""
        key = jax.random.PRNGKey(5)
        centers = jax.random.normal(key, (3, 200))
        dW = jnp.concatenate([
            centers[i] + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), (8, 200)) for i in range(3)])
        M = measures.cosine_similarity_matrix(dW)
        madc_d = np.asarray(measures.madc(M))
        edc_d = np.asarray(measures.edc(dW, m=3))
        iu = np.triu_indices(24, 1)
        a, b = madc_d[iu], edc_d[iu]
        ra = np.argsort(np.argsort(a)).astype(float)
        rb = np.argsort(np.argsort(b)).astype(float)
        rho = np.corrcoef(ra, rb)[0, 1]
        assert rho > 0.75, rho

    def test_embedding_shape(self):
        E, V = measures.edc_embed(_rand(6, 10, 333), m=4)
        assert E.shape == (10, 4) and V.shape == (333, 4)
        assert np.all(np.abs(np.asarray(E)) <= 1 + 1e-5)


class TestSVD:
    @staticmethod
    def _decaying(seed, d, n):
        """Matrix with a decaying spectrum (the FedGroup regime: client
        updates span a few dominant directions). A flat random spectrum is
        adversarial for ANY randomized SVD — not the use case."""
        rng = np.random.default_rng(seed)
        U, _ = np.linalg.qr(rng.normal(size=(d, n)))
        V, _ = np.linalg.qr(rng.normal(size=(n, n)))
        s = 10.0 * 0.6 ** np.arange(n)
        return (U * s) @ V.T

    def test_matches_numpy_svd(self):
        A = self._decaying(7, 80, 20)
        V = np.asarray(randomized_truncated_svd(jnp.asarray(A), 4))
        U_np = np.linalg.svd(A, full_matrices=False)[0][:, :4]
        # subspace angle: |V^T U| ~ identity up to sign/rotation
        S = np.abs(V.T @ U_np)
        np.testing.assert_allclose(np.linalg.svd(S)[1], 1.0, atol=1e-3)

    def test_singular_values(self):
        A = self._decaying(8, 200, 30)
        got = np.sort(np.asarray(truncated_svd_values(jnp.asarray(A), 5)))[::-1]
        want = np.linalg.svd(A, compute_uv=False)[:5]
        np.testing.assert_allclose(got, want, rtol=1e-3)

    def test_orthonormal_columns(self):
        V = randomized_truncated_svd(_rand(9, 500, 16).T, 6)
        G = np.asarray(V.T @ V)
        np.testing.assert_allclose(G, np.eye(6), atol=1e-4)


class TestColdStartMeasure:
    def test_cosine_dissimilarity_range(self):
        a, b = _rand(10, 1, 64)[0], _rand(11, 1, 64)[0]
        d = float(measures.cosine_dissimilarity(a, b))
        assert 0.0 - 1e-6 <= d <= 1.0 + 1e-6
        assert float(measures.cosine_dissimilarity(a, a)) == pytest.approx(0.0, abs=1e-6)
        assert float(measures.cosine_dissimilarity(a, -a)) == pytest.approx(1.0, abs=1e-6)
