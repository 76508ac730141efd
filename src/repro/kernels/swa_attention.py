"""Pallas TPU kernel: sliding-window flash attention (forward).

Used by the long-context (long_500k) variant of the dense architectures and
by Zamba2's shared attention block. Streaming-softmax over KV blocks with
running (max, denom, acc) in VMEM scratch — the classic flash pattern,
windowed: KV blocks entirely outside [q - window + 1, q] are masked out (the
block-index skipping optimization is a §Perf iteration; the baseline visits
every block and masks).

Layout: heads are folded into the grid's first axis; blocks are
(block_q, head_dim) and (block_k, head_dim) — head_dim is the lane dim and
is padded to 128 by the wrapper when needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_q: int, block_k: int, n_kv: int,
            window: int | None, causal: bool, q_offset: int, kv_valid: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale                # (bq, hd)
    k = k_ref[...].astype(jnp.float32)                        # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    ok = kpos < kv_valid                       # mask seq-padding KV slots
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                       # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[...].astype(jnp.float32)                        # (bk, hd)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _finish():
        out_ref[...] = (acc_ref[...] /
                        jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "causal", "block_q", "block_k", "interpret"))
def swa_attention(q, k, v, *, window: int | None = None, causal: bool = True,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool):
    """q: (B, Sq, H, hd); k, v: (B, Sk, H, hd) -> (B, Sq, H, hd).

    Query i sits at absolute position i + (Sk - Sq) (decode-tail alignment,
    matching the jnp oracle).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / hd ** 0.5
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    sq = (Sq + bq - 1) // bq * bq
    sk = (Sk + bk - 1) // bk * bk
    hdp = (hd + 127) // 128 * 128

    # fold (B, H) into one grid axis; pad seq + lane dims
    qf = jnp.pad(q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd),
                 ((0, 0), (0, sq - Sq), (0, hdp - hd)))
    kf = jnp.pad(k.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd),
                 ((0, 0), (0, sk - Sk), (0, hdp - hd)))
    vf = jnp.pad(v.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd),
                 ((0, 0), (0, sk - Sk), (0, hdp - hd)))

    n_kv = sk // bk
    grid = (B * H, sq // bq, n_kv)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=bq, block_k=bk,
                          n_kv=n_kv, window=window, causal=causal,
                          q_offset=Sk - Sq, kv_valid=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, hdp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, hdp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, hdp), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hdp), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, sq, hdp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hdp), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out[:, :Sq, :hd].reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
    return out
