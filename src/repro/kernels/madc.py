"""Pallas TPU kernel: blocked MADC proximity (paper eq. 7).

MADC(i, j) = (1 / (n - 2)) * Σ_{z ≠ i, j} |M_iz − M_jz| for a cosine
similarity matrix M (n, n). The jnp reference broadcasts an (n, n, n)
difference tensor — O(n³) memory — before reducing over z; at the paper's
pre-training scales (n = α·m up to a few hundred) that is already the
dominant allocation of the cold start, and it scales cubically.

This kernel computes the measure tile-by-tile: grid (n/bn, n/bn, n/bz) with
the z axis innermost as the reduction. Each step holds two (bn, bz) row
blocks of M in VMEM and accumulates |M_iz − M_jz| into a (bn, bn) VMEM
scratch, folding the z == i / z == j exclusion (and the padding mask) into
the accumulation instead of materializing and re-masking the full cube.
Peak live memory is O(bn·bz) per step — independent of n — and M is read
from HBM once per (i, j) block row-pair.

Inside a step the tile is built one column at a time: row b of the j block
is broadcast against the whole i block as a (bn, bz) difference and
reduced over the lane (z) axis. Everything stays a 2-D tile, the shape
Mosaic lowers; a 3-D (bn, bn, bz) broadcast does not compile for the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def madc_tiles(n: int) -> tuple:
    """(block_n, block_z) picked from n instead of fixed 128s.

    block_n (sublane) rounds n up to the fp32 tile's 8-row granule, capped
    at 128; block_z (lane) rounds up to the mandatory 128-lane granule,
    capped at 512 (two (bn, bz) input tiles + the (bn, bz) difference stay
    well under VMEM at the cap). Small n therefore stops padding
    to a full 128x128 tile — at n=32 the kernel does 16x less tile work
    than the old fixed blocks.
    """
    bn = min(128, -(-n // 8) * 8)
    bz = min(512, -(-n // 128) * 128)
    return bn, bz


def _kernel(mi_ref, mj_ref, out_ref, acc_ref, *, nz: int, n: int,
            block_n: int, block_z: int):
    i, j, z = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(z == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mi = mi_ref[...].astype(jnp.float32)          # (bn, bz) rows of i block
    shape = (block_n, block_z)
    z_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + z * block_z
    i_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + i * block_n
    # z exclusion of the i side (self-similarity bias, eq. 7) + padding
    # columns; the j side is one scalar per column below
    excl_i = (z_idx == i_idx) | (z_idx >= n)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1)

    # one column of the (bn, bn) tile per j row: a (bn, bz) difference
    # against the broadcast row, reduced over the lane (z) axis — only 2-D
    # tiles and lane reductions, which Mosaic lowers
    def column(b, tile):
        mj = mj_ref[pl.ds(b, 1), :].astype(jnp.float32)      # (1, bz)
        excl = excl_i | (z_idx == j * block_n + b)
        diff = jnp.where(excl, 0.0, jnp.abs(mi - mj))
        col = jnp.sum(diff, axis=1, keepdims=True)            # (bn, 1)
        return jnp.where(lane == b, col, tile)

    acc_ref[...] += jax.lax.fori_loop(
        0, block_n, column, jnp.zeros((block_n, block_n), jnp.float32))

    @pl.when(z == nz - 1)
    def _finish():
        out_ref[...] = acc_ref[...] / max(n - 2, 1)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_z", "interpret"))
def madc_block(M, *, block_n: int | None = None, block_z: int | None = None,
               interpret: bool):
    """M: (n, n) cosine similarities -> (n, n) MADC dissimilarities (fp32).

    Block shapes default to ``madc_tiles(n)`` — sized from n, not fixed
    constants. Wrapper pads rows to block_n and columns to block_z; padded
    rows are sliced away, padded z columns are masked inside the kernel.
    """
    n = M.shape[0]
    tn, tz = madc_tiles(n)
    block_n = tn if block_n is None else block_n
    block_z = tz if block_z is None else block_z
    rn = (n + block_n - 1) // block_n * block_n
    cn = (n + block_z - 1) // block_z * block_z
    Mp = jnp.pad(M.astype(jnp.float32), ((0, rn - n), (0, cn - n)))

    nz = cn // block_z
    grid = (rn // block_n, rn // block_n, nz)
    out = pl.pallas_call(
        functools.partial(_kernel, nz=nz, n=n, block_n=block_n,
                          block_z=block_z),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_z), lambda i, j, z: (i, z)),
            pl.BlockSpec((block_n, block_z), lambda i, j, z: (j, z)),
        ],
        out_specs=pl.BlockSpec((block_n, block_n), lambda i, j, z: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rn, rn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, block_n), jnp.float32)],
        interpret=interpret,
    )(Mp, Mp)
    return out[:n, :n]
