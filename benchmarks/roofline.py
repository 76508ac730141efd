"""Roofline analysis per (arch × shape × mesh) — deliverable (g).

Three terms, in seconds, per training/serving step:

  compute_s    = FLOPs            / (chips × 197 TFLOP/s bf16)
  memory_s     = HBM bytes        / (chips × 819 GB/s)
  collective_s = collective bytes /  (50 GB/s per-chip ICI link)

with the TPU v5e row of ``benchmarks.peaks``.

METHODOLOGY NOTE (verified empirically in this repo): XLA's
``compiled.cost_analysis()`` counts a ``lax.scan`` (while-loop) body ONCE,
not ×trip-count — a 61-layer scanned model reports ~1/61 of its real FLOPs.
All our models scan over layers, so the compute/memory terms here come from
an ANALYTIC model (below), cross-checked against cost_analysis on unrolled
reduced variants. The collective term reads the dry-run JSON, whose parser
multiplies collectives inside while-body computations by the layer trip
count.

Analytic model (documented assumptions):
  * matmul FLOPs = 2 × (active matmul params) × tokens; backward ×3 total.
    Active params from jax.eval_shape — exact; MoE expert tensors scaled by
    top_k·capacity_factor/E; embedding excluded unless tied (gather ≠ matmul).
  * attention: 4·L·B·S·S_eff·H·hd fwd (causal ⇒ ×0.5), S_eff=min(S,window);
    MLA uses (qk_nope+qk_rope+v)/2·hd-equivalent per head.
  * SSD: intra-chunk 4·B·S·Q·H·(N+P) + state path 4·B·S·H·P·N.
  * mLSTM ≈ 6·B·S·H·P² (matrix-memory update + readout); sLSTM ≈ 16·B·S·D·dh.
  * HBM traffic: train = 28 B/param (fp32 w,m,v read+write + grad) +
    3 × activation bytes; prefill/decode = 2 B/param (bf16 read) + cache r/w
    + activation bytes. Uniform sharding over chips is assumed for the
    per-chip division (the specs shard every large tensor).
"""
from __future__ import annotations

import json
import math
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.peaks import device_peaks                              # noqa: E402
from repro.configs import registry, shapes as shp                      # noqa: E402
from repro.models import zoo                                          # noqa: E402

# the production target of launch/mesh.py: a TPU v5e pod
TARGET_KIND = "TPU v5 lite"

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")


# ---------------------------------------------------------------------------
# Parameter census
# ---------------------------------------------------------------------------

def param_census(cfg: zoo.ArchConfig):
    """(total_params, active_matmul_params, embed_params) from eval_shape."""
    params = jax.eval_shape(lambda: zoo.init_params(jax.random.PRNGKey(0), cfg))
    total = active = embed = 0
    moe_scale = 1.0
    if cfg.n_experts:
        moe_scale = min(1.0, cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        names = [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]
        n = int(np.prod(leaf.shape))
        total += n
        if "embed" in names:
            embed += n
            if cfg.tie_embeddings:
                active += n        # tied: also the output matmul
            continue
        if leaf.ndim < 2 or (names and "blocks" in names and leaf.ndim < 3
                             and "moe" not in names):
            continue               # 1-D norms/biases: no matmul flops
        if "moe" in names and leaf.ndim == 4:      # stacked (L,E,D,F)
            active += int(n * moe_scale)
        else:
            active += n
    return total, active, embed


# ---------------------------------------------------------------------------
# Analytic FLOPs / bytes
# ---------------------------------------------------------------------------

def mixer_flops_fwd(cfg: zoo.ArchConfig, B: int, S: int, ctx: int | None = None):
    """Sequence-mixing FLOPs (attention scores/AV or SSM state path), fwd."""
    L = cfg.n_layers
    if ctx is None:
        ctx = S
    s_eff = min(ctx, cfg.window) if cfg.window else ctx
    causal_half = 0.5 if (cfg.causal and S > 1) else 1.0

    if cfg.family in ("dense", "vlm", "audio"):
        return 4.0 * L * B * S * s_eff * cfg.n_heads * cfg.hd * causal_half
    if cfg.family == "moe":
        if cfg.mla:
            per_head = cfg.qk_nope + cfg.qk_rope + cfg.v_head_dim
            return 2.0 * L * B * S * s_eff * cfg.n_heads * per_head * causal_half
        return 4.0 * L * B * S * s_eff * cfg.n_heads * cfg.hd * causal_half
    if cfg.family == "hybrid":
        di = cfg.ssm_expand * cfg.d_model
        H, P, N = di // cfg.ssm_head_dim, cfg.ssm_head_dim, cfg.ssm_state
        Q = cfg.ssd_chunk
        ssd = L * B * S * (4.0 * Q * H * (N + P) * 0.5 + 4.0 * H * P * N) \
            if S > 1 else L * B * 4.0 * H * P * N
        n_shared = L // cfg.shared_attn_period if cfg.shared_attn_period else 0
        attn = 4.0 * n_shared * B * S * s_eff * cfg.n_heads * cfg.hd * causal_half
        return ssd + attn
    if cfg.family == "ssm":                       # xLSTM
        di = cfg.mlstm_proj_factor * cfg.d_model
        P = di // cfg.n_heads
        n_m = sum(1 for k in cfg.xlstm_pattern if k == "m")
        n_s = len(cfg.xlstm_pattern) - n_m
        dh = cfg.d_model // cfg.n_heads
        return (6.0 * n_m * B * S * cfg.n_heads * P * P
                + 16.0 * n_s * B * S * cfg.d_model * dh)
    raise ValueError(cfg.family)


def activation_bytes_fwd(cfg: zoo.ArchConfig, B: int, S: int) -> float:
    """Rough per-step activation traffic (bf16), ~12 tensor r/w per layer."""
    return 12.0 * cfg.n_layers * B * S * cfg.d_model * 2.0


def analytic_terms(cfg: zoo.ArchConfig, shape: shp.InputShape, chips: int):
    peaks = device_peaks(TARGET_KIND)
    B, S = shape.global_batch, shape.seq_len
    total, active, embed = param_census(cfg)
    if shape.kind == "train":
        tokens = B * S
        flops = 3.0 * (2.0 * active * tokens + mixer_flops_fwd(cfg, B, S))
        bytes_ = 28.0 * total + 3.0 * activation_bytes_fwd(cfg, B, S)
        model_flops = 6.0 * active * tokens
    elif shape.kind == "prefill":
        tokens = B * S
        flops = 2.0 * active * tokens + mixer_flops_fwd(cfg, B, S)
        bytes_ = 2.0 * total + activation_bytes_fwd(cfg, B, S)
        model_flops = 2.0 * active * tokens
    else:  # decode: ONE token, context = S
        tokens = B
        flops = 2.0 * active * tokens + mixer_flops_fwd(cfg, B, 1, ctx=S)
        cache = cache_bytes(cfg, B, S)
        bytes_ = 2.0 * total + 2.0 * cache + activation_bytes_fwd(cfg, B, 1)
        model_flops = 2.0 * active * tokens
    return {
        "flops": flops, "bytes": bytes_, "model_flops": model_flops,
        "params_total": total, "params_active": active,
        "compute_s": flops / (chips * peaks["flops_bf16"]),
        "memory_s": bytes_ / (chips * peaks["hbm_bw"]),
    }


def cache_bytes(cfg: zoo.ArchConfig, B: int, S: int) -> float:
    eff = min(S, cfg.window) if cfg.window else S
    if cfg.family in ("dense", "vlm"):
        return 2.0 * cfg.n_layers * B * eff * cfg.n_kv_heads * cfg.hd * 2
    if cfg.family == "moe":
        if cfg.mla:
            return cfg.n_layers * B * eff * (cfg.kv_rank + cfg.qk_rope) * 2
        return 2.0 * cfg.n_layers * B * eff * cfg.n_kv_heads * cfg.hd * 2
    if cfg.family == "hybrid":
        di = cfg.ssm_expand * cfg.d_model
        H, P, N = di // cfg.ssm_head_dim, cfg.ssm_head_dim, cfg.ssm_state
        ssm = cfg.n_layers * B * H * P * N * 2
        n_shared = cfg.n_layers // cfg.shared_attn_period if cfg.shared_attn_period else 0
        attn = 2.0 * n_shared * B * eff * cfg.n_kv_heads * cfg.hd * 2
        return ssm + attn
    if cfg.family == "ssm":
        di = cfg.mlstm_proj_factor * cfg.d_model
        P = di // cfg.n_heads
        return cfg.n_layers * B * cfg.n_heads * P * P * 4
    return 0.0


# ---------------------------------------------------------------------------
# Assemble the table from dry-run JSONs
# ---------------------------------------------------------------------------

def load_dryrun(arch: str, shape: str, mesh: str, suffix: str = ""):
    p = os.path.join(DRYRUN_DIR, f"{arch}_{shape}_{mesh}{suffix}.json")
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return json.load(f)


def row_for(arch: str, shape_name: str, mesh: str = "16x16",
            suffix: str = ""):
    base = registry.get(arch)
    shape = shp.SHAPES[shape_name]
    ok, why = shp.supported(base, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skip",
                "reason": why}
    cfg = shp.config_for(base, shape)
    chips = int(np.prod([int(x) for x in mesh.split("x")]))
    terms = analytic_terms(cfg, shape, chips)
    rec = load_dryrun(arch, shape_name, mesh, suffix)
    coll_bytes = rec["collective_bytes_total"] if rec else 0.0
    collective_s = coll_bytes / device_peaks(TARGET_KIND)["ici_bw"]
    dom = max(("compute", terms["compute_s"]), ("memory", terms["memory_s"]),
              ("collective", collective_s), key=lambda kv: kv[1])
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh, "status": "ok",
        "compute_s": terms["compute_s"], "memory_s": terms["memory_s"],
        "collective_s": collective_s, "dominant": dom[0],
        "model_flops": terms["model_flops"], "hlo_flops_analytic": terms["flops"],
        "useful_ratio": terms["model_flops"] / max(terms["flops"], 1),
        "params_total": terms["params_total"],
        "params_active": terms["params_active"],
        "dryrun": bool(rec),
        "mem_gib_args": (rec or {}).get("memory_analysis", {}).get(
            "argument_size_in_bytes", 0) / 2**30,
        "mem_gib_temp": (rec or {}).get("memory_analysis", {}).get(
            "temp_size_in_bytes", 0) / 2**30,
    }


def full_table(mesh: str = "16x16"):
    rows = []
    for arch in registry.ARCHS:
        for shape_name in shp.SHAPES:
            rows.append(row_for(arch, shape_name, mesh))
    return rows


def print_table(rows):
    print(f"\n# Roofline — per (arch × shape), terms in ms/step "
          f"(chips on mesh share the work)")
    hdr = (f"{'arch':>22} {'shape':>11} {'compute':>9} {'memory':>9} "
           f"{'collect':>9} {'dominant':>10} {'useful%':>8} "
           f"{'argGiB':>7} {'tmpGiB':>7}")
    print(hdr)
    for r in rows:
        if r["status"] != "ok":
            print(f"{r['arch']:>22} {r['shape']:>11} "
                  f"{'— skip: ' + r['reason']}")
            continue
        print(f"{r['arch']:>22} {r['shape']:>11} "
              f"{r['compute_s']*1e3:>9.2f} {r['memory_s']*1e3:>9.2f} "
              f"{r['collective_s']*1e3:>9.2f} {r['dominant']:>10} "
              f"{100*r['useful_ratio']:>7.1f}% "
              f"{r['mem_gib_args']:>7.1f} {r['mem_gib_temp']:>7.1f}")


def main(quick: bool = False):
    rows = full_table("16x16")
    print_table(rows)
    out = os.path.join(DRYRUN_DIR, "..", "roofline_16x16.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1, default=float)
    print(f"\nwrote {os.path.abspath(out)}")
    if not quick:
        rows2 = full_table("2x16x16")
        print("\n## multi-pod (2x16x16, 512 chips)")
        print_table(rows2)
        out2 = os.path.join(DRYRUN_DIR, "..", "roofline_2x16x16.json")
        with open(out2, "w") as f:
            json.dump(rows2, f, indent=1, default=float)
    return rows


if __name__ == "__main__":
    main()
