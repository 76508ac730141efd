"""Pallas TPU kernel: Mamba2 SSD intra-chunk block (the hybrid archs' compute
hot spot).

For each (batch·head, chunk) grid cell, computes the two dense pieces of the
chunked SSD algorithm entirely in VMEM:

  Y_diag = (C Bᵀ ⊙ L) X        with L[i,j] = exp(a_i − a_j) for j ≤ i
  state  = (B ⊙ exp(a_Q − a))ᵀ X     (the chunk's contribution to the
                                      inter-chunk recurrence)

a = inclusive cumsum of the per-step log decays (dt·A). The sequential
inter-chunk recurrence stays outside (it is O(seq/Q) tiny updates); this
kernel is the MXU-heavy part. Block shapes are (Q, P) / (Q, N) tiles padded
to the 128-lane boundary by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, st_ref, *, q: int):
    x = x_ref[...].astype(jnp.float32)          # (Q, P)
    a = a_ref[...].astype(jnp.float32)[:, 0]    # (Q,)
    b = b_ref[...].astype(jnp.float32)          # (Q, N)
    c = c_ref[...].astype(jnp.float32)          # (Q, N)

    diff = a[:, None] - a[None, :]              # (Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(jj <= ii, jnp.exp(diff), 0.0)

    s = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * L
    y_ref[...] = jax.lax.dot_general(
        s, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    decay_last = jnp.exp(a[-1] - a)             # (Q,)
    bw = b * decay_last[:, None]
    st_ref[...] = jax.lax.dot_general(
        bw, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(X, A_cs, B, C, *, interpret: bool):
    """X: (BH, NC, Q, P); A_cs: (BH, NC, Q) inclusive-cumsum log decays;
    B, C: (BH, NC, Q, N). Returns (Y_diag (BH,NC,Q,P) fp32,
    states (BH,NC,N,P) fp32)."""
    BH, NC, Q, P = X.shape
    N = B.shape[-1]
    pp = (P + 127) // 128 * 128
    np_ = (N + 127) // 128 * 128

    Xp = jnp.pad(X, ((0, 0), (0, 0), (0, 0), (0, pp - P)))
    Ap = A_cs[..., None]                                    # (BH,NC,Q,1)
    Bp = jnp.pad(B, ((0, 0), (0, 0), (0, 0), (0, np_ - N)))
    Cp = jnp.pad(C, ((0, 0), (0, 0), (0, 0), (0, np_ - N)))

    grid = (BH, NC)
    y, st = pl.pallas_call(
        functools.partial(_kernel, q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, Q, pp), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, None, Q, 1), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, None, Q, np_), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, None, Q, np_), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, Q, pp), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, None, np_, pp), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, NC, Q, pp), jnp.float32),
            jax.ShapeDtypeStruct((BH, NC, np_, pp), jnp.float32),
        ],
        interpret=interpret,
    )(Xp, Ap, Bp, Cp)
    return y[..., :P], st[..., :N, :P]
