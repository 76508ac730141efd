"""Pallas TPU kernel: fused cosine-similarity block for the EDC measure.

E = K(ΔW, Vᵀ): the paper's eq. 8 inner loop — the perf-critical stage of the
EDC group cold start when d_w is large (ΔW is HDLSS: n ~ α·m clients, d_w up
to hundreds of millions). The MADC branch has its own fused measure kernel
(``kernels.madc.madc_block``, eq. 7); both are exposed via ``kernels.ops``.

Fusion: one HBM pass over ΔW per row-block computes BOTH the dot products
ΔW·V and the row norms ‖ΔW_i‖ (the reference implementation reads ΔW twice).
Tiling: grid (n/bn, d/bd); the d axis is the reduction — partial products
accumulate into VMEM scratch, normalization happens on the last d-step.
Block shapes are MXU-aligned (multiples of 128 on the contracting/lane dims);
m (number of groups) is padded to the 128-lane tile by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-12


def _kernel(dw_ref, v_ref, vnorm_ref, out_ref, acc_ref, nrm_ref, *, nd: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        nrm_ref[...] = jnp.zeros_like(nrm_ref)

    dw = dw_ref[...].astype(jnp.float32)          # (bn, bd)
    v = v_ref[...].astype(jnp.float32)            # (bd, m)
    acc_ref[...] += jax.lax.dot_general(
        dw, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    nrm_ref[...] += jnp.sum(jnp.square(dw), axis=1, keepdims=True)

    @pl.when(j == nd - 1)
    def _finish():
        rn = jnp.sqrt(nrm_ref[...])               # (bn, 1)
        denom = jnp.maximum(rn * vnorm_ref[...], _EPS)
        out_ref[...] = acc_ref[...] / denom


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def edc_cosine(dW, V, *, block_n: int = 128, block_d: int = 512,
               interpret: bool):
    """dW: (n, d), V: (d, m) -> (n, m) cosine similarities (fp32).

    Wrapper pads n to block_n, d to block_d and m to the 128-lane tile.
    """
    n, d = dW.shape
    m = V.shape[1]
    mp = (m + 127) // 128 * 128
    np_ = (n + block_n - 1) // block_n * block_n
    dp = (d + block_d - 1) // block_d * block_d

    dWp = jnp.pad(dW, ((0, np_ - n), (0, dp - d)))
    Vp = jnp.pad(V, ((0, dp - d), (0, mp - m)))
    vnorm = jnp.linalg.norm(Vp.astype(jnp.float32), axis=0, keepdims=True)
    vnorm = jnp.maximum(vnorm, _EPS)              # (1, mp)

    nd = dp // block_d
    grid = (np_ // block_n, nd)
    out = pl.pallas_call(
        functools.partial(_kernel, nd=nd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_d, mp), lambda i, j: (j, 0)),
            pl.BlockSpec((1, mp), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, mp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, mp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_n, mp), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(dWp, Vp, vnorm)
    return out[:n, :m]
