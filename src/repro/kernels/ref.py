"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-12


def cosine_block_ref(dW, V):
    """E[i, j] = <ΔW_i, V_:,j> / (||ΔW_i|| ||V_:,j||).

    dW: (n, d); V: (d, m) -> (n, m) float32. The products are full f32 on
    every backend (a TPU's default matmul precision rounds to bf16).
    """
    dW32 = dW.astype(jnp.float32)
    V32 = V.astype(jnp.float32)
    dots = jnp.matmul(dW32, V32, precision=jax.lax.Precision.HIGHEST)
    rn = jnp.linalg.norm(dW32, axis=1, keepdims=True)
    cn = jnp.linalg.norm(V32, axis=0, keepdims=True)
    return dots / jnp.maximum(rn * cn, _EPS)


def swa_attention_ref(q, k, v, *, window: int | None, causal: bool = True,
                      scale: float | None = None):
    """Dense masked softmax attention oracle.

    q: (B, Sq, H, hd); k, v: (B, Sk, H, hd). Query i is at absolute position
    i + (Sk - Sq) (decode tail alignment). Returns (B, Sq, H, hd) in fp32.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = 1.0 / hd ** 0.5
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
    kpos = jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(ok[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def ssd_chunk_ref(X, dtA, B, C):
    """Single-chunk SSD oracle via the sequential recurrence.

    X: (b, q, h, p); dtA: (b, q, h); B, C: (b, q, h, n).
    Returns (Y (b,q,h,p), final_state (b,h,p,n)), all fp32.
    """
    b, q, h, p = X.shape
    n = B.shape[-1]
    X32, A32 = X.astype(jnp.float32), dtA.astype(jnp.float32)
    B32, C32 = B.astype(jnp.float32), C.astype(jnp.float32)

    def step(state, t):
        dec = jnp.exp(A32[:, t])[..., None, None]              # (b,h,1,1)
        state = dec * state + jnp.einsum("bhp,bhn->bhpn", X32[:, t], B32[:, t])
        y = jnp.einsum("bhpn,bhn->bhp", state, C32[:, t])
        return state, y

    state0 = jnp.zeros((b, h, p, n), jnp.float32)
    final, ys = jax.lax.scan(step, state0, jnp.arange(q))
    return ys.transpose(1, 0, 2, 3), final
