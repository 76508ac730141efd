"""Clustering backends: K-Means++ (JAX) and hierarchical complete linkage."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.cluster import hierarchical, kmeans_inertia, kmeans_pp


def _blobs(seed, k=3, per=10, dim=4, sep=8.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 1, (k, dim))
    centers *= sep / np.linalg.norm(centers, axis=1, keepdims=True)
    X = np.concatenate([c + rng.normal(0, 0.3, (per, dim)) for c in centers])
    y = np.repeat(np.arange(k), per)
    return X.astype(np.float32), y


def _purity(labels, truth):
    total = 0
    for lbl in np.unique(labels):
        members = truth[labels == lbl]
        total += np.bincount(members).max()
    return total / len(truth)


class TestKMeansPP:
    def test_recovers_blobs(self):
        X, y = _blobs(0)
        assign, centers = kmeans_pp(jax.random.PRNGKey(0), jnp.asarray(X), 3)
        assert _purity(np.asarray(assign), y) == 1.0

    def test_inertia_below_random(self):
        X, y = _blobs(1, k=4, per=12)
        assign, centers = kmeans_pp(jax.random.PRNGKey(1), jnp.asarray(X), 4)
        good = float(kmeans_inertia(jnp.asarray(X), assign, centers))
        rng = np.random.default_rng(0)
        rand_assign = jnp.asarray(rng.integers(0, 4, len(X)))
        rand_centers = jnp.asarray(rng.normal(0, 1, (4, X.shape[1])).astype(np.float32))
        bad = float(kmeans_inertia(jnp.asarray(X), rand_assign, rand_centers))
        assert good < bad / 5

    @given(st.integers(0, 1000))
    @settings(max_examples=10, deadline=None)
    def test_all_clusters_valid(self, seed):
        X, _ = _blobs(seed, k=3, per=6)
        assign, _ = kmeans_pp(jax.random.PRNGKey(seed), jnp.asarray(X), 3)
        a = np.asarray(assign)
        assert a.min() >= 0 and a.max() < 3


class TestHierarchical:
    def test_recovers_blobs_from_distance(self):
        X, y = _blobs(2)
        D = np.linalg.norm(X[:, None] - X[None], axis=-1)
        labels = hierarchical(D, 3)
        assert _purity(labels, y) == 1.0

    def test_k_clusters(self):
        X, _ = _blobs(3, k=4, per=5)
        D = np.linalg.norm(X[:, None] - X[None], axis=-1)
        labels = hierarchical(D, 4)
        assert len(np.unique(labels)) == 4

    def test_trivial_k_equals_n(self):
        X, _ = _blobs(4, k=2, per=3)
        D = np.linalg.norm(X[:, None] - X[None], axis=-1)
        labels = hierarchical(D, len(X))
        assert len(np.unique(labels)) == len(X)


def _hierarchical_submatrix(proximity, k):
    """The retired implementation: rebuilds D[np.ix_(active, active)] on
    every merge (an extra O(n²) copy per step) — kept verbatim as the
    equivalence oracle for the masked-argmin rewrite."""
    D = np.array(proximity, dtype=np.float64, copy=True)
    n = D.shape[0]
    np.fill_diagonal(D, np.inf)
    active = list(range(n))
    members = {i: [i] for i in range(n)}
    while len(active) > k:
        sub = D[np.ix_(active, active)]
        flat = np.argmin(sub)
        ai, aj = np.unravel_index(flat, sub.shape)
        i, j = active[ai], active[aj]
        if j < i:
            i, j = j, i
        for other in active:
            if other in (i, j):
                continue
            D[i, other] = D[other, i] = max(D[i, other], D[j, other])
        members[i].extend(members.pop(j))
        active.remove(j)
    labels = np.zeros(n, dtype=np.int32)
    for lbl, root in enumerate(active):
        for idx in members[root]:
            labels[idx] = lbl
    return labels


class TestHierarchicalMaskedArgminEquivalence:
    """The masked-argmin rewrite (argmin over the full +inf-masked matrix,
    vectorized linkage update) must reproduce the submatrix version label
    for label — including under ties, where both argmin orders agree
    because the active set stays ascending."""

    @given(st.integers(0, 500))
    @settings(max_examples=25, deadline=None)
    def test_matches_submatrix_version(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(3, 25))
        k = int(rng.integers(1, n))
        A = rng.random((n, n))
        D = (A + A.T) / 2
        if seed % 3 == 0:
            D = np.round(D, 1)          # quantize to force argmin ties
        np.fill_diagonal(D, 0)
        np.testing.assert_array_equal(hierarchical(D, k),
                                      _hierarchical_submatrix(D, k))
