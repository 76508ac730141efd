"""Jit'd public wrappers around the Pallas kernels.

Every call picks the kernel mode from the platform of its input: compiled
through Mosaic on a TPU, interpreted on the CPU backend (the kernel body
executes step by step in Python — faithful to the TPU grid semantics). The
input's own device decides for a concrete array; inside a trace, or for a
host array, the default backend does.
"""
from __future__ import annotations

import jax

from repro.kernels.edc_cosine import edc_cosine
from repro.kernels.madc import madc_block as _madc_block
from repro.kernels.ssd_chunk import ssd_intra_chunk
from repro.kernels.swa_attention import swa_attention

# MADC kernel/reference crossovers: below these the O(n³)-broadcast
# reference is faster than the kernel's tiling overhead, so
# measures.madc(use_kernel=True) falls back to it. Interpret mode executes
# the grid step-by-step in Python — there the kernel only pays off once the
# reference's (n, n, n) cube itself becomes the problem (n=512 -> 512 MB
# fp32); through Mosaic the crossover is the tile scale (an estimate, not
# yet measured on a chip).
MADC_CROSSOVER_COMPILED_N = 128
MADC_CROSSOVER_INTERPRET_N = 512


def interpret_mode(x=None) -> bool:
    """True when a kernel call on ``x`` must run in the Pallas interpreter:
    ``x`` lives on the CPU backend, or (tracer / host array / None) the
    default backend is the CPU."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform == "cpu"
    return jax.default_backend() == "cpu"


def madc_crossover_n(M=None) -> int:
    """Kernel-vs-reference crossover for the mode a call on ``M`` runs in."""
    return (MADC_CROSSOVER_INTERPRET_N if interpret_mode(M)
            else MADC_CROSSOVER_COMPILED_N)


def cosine_block(dW, V, **kw):
    """Fused cosine-similarity block E = K(ΔW, Vᵀ) (paper eq. 8)."""
    kw.setdefault("interpret", interpret_mode(dW))
    return edc_cosine(dW, V, **kw)


def madc_block(M, **kw):
    """Blocked MADC proximity matrix (paper eq. 7), O(bn²) memory."""
    kw.setdefault("interpret", interpret_mode(M))
    return _madc_block(M, **kw)


def sliding_window_attention(q, k, v, *, window=None, causal=True, **kw):
    """Flash-style sliding-window attention forward."""
    kw.setdefault("interpret", interpret_mode(q))
    return swa_attention(q, k, v, window=window, causal=causal, **kw)


def ssd_chunk_block(X, A_cs, B, C, **kw):
    """Mamba2 SSD intra-chunk block (Y_diag + chunk states)."""
    kw.setdefault("interpret", interpret_mode(X))
    return ssd_intra_chunk(X, A_cs, B, C, **kw)
