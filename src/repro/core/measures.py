"""Data-driven distance measures for client clustering (paper §3.3).

  cosine_similarity_matrix  M_ij = S(i,j)                      (eq. 5/6)
  madc                      mean abs. diff of pairwise cosines (eq. 7)
  edc_embed / edc           decomposed cosine embedding         (eq. 8)

EDC first truncates ΔWᵀ to its top-m singular directions V, then embeds each
client as its cosine similarities to those directions; the Euclidean distance
of the embeddings ("EDC") approximates MADC at O(m² d_w) instead of
O(n² d_w) and — unlike raw ℓp on HDLSS vectors — does not suffer distance
concentration.

The inner product blocks here delegate to the Pallas kernel wrapper in
``repro.kernels.ops`` when ``use_kernel=True`` (TPU path); the default is the
pure-jnp path that XLA fuses fine on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.svd import randomized_truncated_svd

_EPS = 1e-12


def row_normalize(x):
    n = jnp.linalg.norm(x, axis=-1, keepdims=True)
    return x / jnp.maximum(n, _EPS)


def cosine_similarity_matrix(dw_a, dw_b=None):
    """K(A, B): (n, q) pairwise cosine similarities. dw_*: (n, d) / (q, d)."""
    a = row_normalize(dw_a)
    b = a if dw_b is None else row_normalize(dw_b)
    return jnp.clip(a @ b.T, -1.0, 1.0)


def madc(M, use_kernel: bool = False, min_kernel_n: int | None = None):
    """Mean-of-Absolute-Differences of pairwise Cosines (eq. 7).

    M: (n, n) cosine similarity matrix -> (n, n) dissimilarity matrix.
    The z != i, j exclusion removes the self-similarity observation bias.

    ``use_kernel=True`` delegates to the blocked Pallas kernel
    (``kernels.ops.madc_block``), which streams M in (bn, bz) tiles instead
    of materializing this reference's O(n³) broadcast — but only at or
    above the measured crossover size (``kernels.ops.madc_crossover_n``);
    below it the reference is faster than the kernel's tiling overhead and
    this dispatch automatically falls back to it. ``min_kernel_n``
    overrides the crossover (0 forces the kernel path — tests/benchmarks).
    """
    if use_kernel:
        from repro.kernels.ops import madc_block, madc_crossover_n
        cut = madc_crossover_n(M) if min_kernel_n is None else min_kernel_n
        if M.shape[0] >= cut:
            return madc_block(M)
    n = M.shape[0]
    diff = jnp.abs(M[:, None, :] - M[None, :, :])        # (n, n, n) over z
    eye = jnp.eye(n, dtype=bool)
    excl = eye[:, None, :] | eye[None, :, :]             # z == i or z == j
    s = jnp.sum(jnp.where(excl, 0.0, diff), axis=-1)
    return s / max(n - 2, 1)


def edc_embed(dW, m: int, key=None, use_kernel: bool = False):
    """Decompose ΔW into m singular directions and embed clients.

    dW: (n, d_w) parameter updates. Returns (E (n, m), V (d_w, m)).
    """
    V = randomized_truncated_svd(dW.T, m, key=key)        # (d_w, m)
    if use_kernel:
        from repro.kernels.ops import cosine_block
        E = cosine_block(dW, V)
    else:
        E = cosine_similarity_matrix(dW, V.T)             # (n, m)
    return E, V


def edc_from_embedding(E, m: int):
    """EDC(i,j) = ||E_i - E_j|| / m (eq. 8)."""
    d2 = jnp.sum(jnp.square(E[:, None, :] - E[None, :, :]), -1)
    return jnp.sqrt(jnp.maximum(d2, 0.0)) / m


def edc(dW, m: int, key=None):
    E, _ = edc_embed(dW, m, key)
    return edc_from_embedding(E, m)


def cosine_dissimilarity(a, b):
    """Normalized cosine dissimilarity in [0, 1] (eq. 9 argument)."""
    num = jnp.vdot(a, b)
    den = jnp.maximum(jnp.linalg.norm(a) * jnp.linalg.norm(b), _EPS)
    return (-num / den + 1.0) / 2.0
