"""Benchmark: clustering-measure cost — the paper's efficiency claim.

Pairwise-cosine/MADC cost O(n² d_w) vs EDC O(m² d_w) (+randomized SVD).
Measures wall time for growing d_w at fixed n (pre-training clients) and
reports the derived FLOP counts. Also times the MADC dispatch
(``measures.madc(use_kernel=True)`` — blocked Pallas kernel at or above the
measured crossover size, automatic fallback to the reference below it) and
the raw kernel in interpret mode (correctness path; on-TPU numbers come
from the roofline) vs the O(n³)-broadcast reference, with the analytic
peak-memory model showing the kernel's working set is tile-sized while the
reference grows as n³.

Results (including the crossover and both kernel trajectories) persist to
BENCH_clustering.json; a >2x drop of the dispatch's relative speed vs the
committed baseline flags a regression (exit gate in benchmarks/run.py).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bench_io import interleaved_best, record_run
from repro.core import measures
from repro.core.svd import randomized_truncated_svd
from repro.kernels.madc import madc_tiles
from repro.kernels.ops import madc_crossover_n


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us




def _madc_memory_model(n: int) -> dict:
    """Peak transient bytes (fp32): the reference materializes the (n, n, n)
    |M_iz − M_jz| cube; the blocked kernel holds two (bn, bz) input tiles,
    one (bn, bz) difference, the (bn, bn) accumulator and the (bn, bn) tile
    it builds column by column — tile-sized (madc_tiles picks (bn, bz)
    from n, capped at (128, 512))."""
    ref = 4 * n * n * n
    bn, bz = madc_tiles(n)
    kern = 4 * (3 * bn * bz + 2 * bn * bn)
    return {"n": n, "ref_peak_bytes": ref, "kernel_tile_bytes": kern}


def main(quick: bool = False):
    n, m = 60, 3
    dims = [2048, 16384] if quick else [2048, 16384, 131072, 1048576]
    print("\n# Clustering measure cost (n=60 pretrain clients, m=3 groups)")
    print(f"{'d_w':>9} {'pairwise_us':>12} {'madc_us':>10} {'edc_us':>10} "
          f"{'pairwise_flops':>14} {'edc_flops':>11}")
    rows = []
    key = jax.random.PRNGKey(0)
    madc_j = jax.jit(lambda W: measures.madc(measures.cosine_similarity_matrix(W)))
    pair_j = jax.jit(measures.cosine_similarity_matrix)

    def edc_fn(W):
        V = randomized_truncated_svd(W.T, m)
        return measures.cosine_similarity_matrix(W, V.T)
    edc_j = jax.jit(edc_fn)

    for d in dims:
        W = jax.random.normal(key, (n, d))
        t_pair = _time(pair_j, W)
        t_madc = _time(madc_j, W)
        t_edc = _time(edc_j, W)
        f_pair = 2 * n * n * d
        f_edc = 2 * n * m * d + 4 * (m + 8) ** 2 * d   # embed + rsvd passes
        print(f"{d:>9} {t_pair:>12.0f} {t_madc:>10.0f} {t_edc:>10.0f} "
              f"{f_pair:>14.2e} {f_edc:>11.2e}")
        rows.append({"d_w": d, "pairwise_us": t_pair, "madc_us": t_madc,
                     "edc_us": t_edc})

    # -- MADC dispatch (kernel above crossover, reference below) vs ref ----
    # madc(use_kernel=True) falls back to the reference below the measured
    # crossover, so at the benchmarked (sub-crossover) sizes the dispatch
    # must never lose to the reference: rel_speed ≈ 1.0 is the contract the
    # gate watches. The raw kernel (crossover forced to 0) is timed
    # separately to keep the tile-work trajectory (tiles now sized from n).
    sizes = [32, 64] if quick else [32, 64, 96, 128]
    crossover = madc_crossover_n()
    print(f"\n# MADC: dispatch (crossover n={crossover}) and raw blocked "
          f"kernel (interpret) vs (n,n,n) reference")
    print(f"{'n':>5} {'ref_us':>10} {'dispatch_us':>12} {'kernel_us':>10} "
          f"{'ref_peak_bytes':>15} {'kernel_tile_bytes':>18}")
    kern_rows = []
    ref_j = jax.jit(measures.madc)
    disp_j = jax.jit(lambda M: measures.madc(M, use_kernel=True))
    kern_j = lambda M: measures.madc(M, use_kernel=True, min_kernel_n=0)
    for nn in sizes:
        W = jax.random.normal(jax.random.fold_in(key, nn), (nn, 256))
        M = jax.block_until_ready(measures.cosine_similarity_matrix(W))
        t_ref, t_disp, t_kern = interleaved_best(
            [lambda f=f: jax.block_until_ready(f(M))
             for f in (ref_j, disp_j, kern_j)],
            reps=10 if quick else 20)
        mem = _madc_memory_model(nn)
        print(f"{nn:>5} {t_ref:>10.0f} {t_disp:>12.0f} {t_kern:>10.0f} "
              f"{mem['ref_peak_bytes']:>15} {mem['kernel_tile_bytes']:>18}")
        kern_rows.append({**mem, "ref_us": t_ref, "dispatch_us": t_disp,
                          "kernel_us": t_kern})
    # kernel_tile_bytes comes from the analytic model — the measured
    # counterpart is the on-TPU roofline's job; the ref column is exact
    # (jnp really allocates the (n, n, n) cube)
    tile_bytes = kern_rows[-1]["kernel_tile_bytes"]

    # relative speed is machine-stable; raw interpret-mode wall time is not.
    # The watched metric is the user-facing dispatch at the largest size.
    largest = kern_rows[-1]
    rel = largest["ref_us"] / max(largest["dispatch_us"], 1e-9)
    rel_raw = largest["ref_us"] / max(largest["kernel_us"], 1e-9)
    metrics = {
        "quick": quick,
        "measure_cost": rows,
        "madc_kernel": kern_rows,
        "madc_kernel_rel_speed": rel,
        "madc_raw_kernel_rel_speed": rel_raw,
        "madc_kernel_crossover_n": crossover,
        "kernel_tile_bytes": tile_bytes,
    }
    # Below the crossover the dispatch IS the reference, so this ratio is
    # ≈1.0 by construction and only jitters with host load; the behavioral
    # fallback is unit-tested (test_kernels), and this gate is a coarse
    # wall-clock backstop — hence the wider factor than the default 2x.
    regression, details = record_run(
        "BENCH_clustering.json", metrics,
        watch=[("madc_kernel_rel_speed", "min")], factor=3.0)
    if regression:
        print("REGRESSION:", "; ".join(details))
    return {"rows": len(rows), "madc_rel_speed": round(rel, 3),
            "madc_raw_rel_speed": round(rel_raw, 3),
            "crossover_n": crossover,
            "regression": regression, "regression_details": details}


if __name__ == "__main__":
    main()
