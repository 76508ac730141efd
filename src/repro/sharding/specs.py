"""PartitionSpec rules: per-architecture tensor parallelism + the federated
round executor's 2-D ``(data, model)`` placement.

Two families of specs live here:

  * the per-architecture rules below (``param_specs`` / ``state_specs`` /
    ``data_specs`` / ``cache_specs``) used by the launch dry-runs, and
  * the *federated-round* specs (``cohort_pspec`` / ``group_param_pspec`` /
    ``group_param_specs`` / ``data_axis_names``) used by
    ``fed.parallel.make_sharded_executor``: the vmapped client batch shards
    its leading (client) axis over the mesh's data axes, and the m-stacked
    group parameters shard their largest divisible non-group dim over
    "model" — replicated when the model axis has size 1, so the 1-device
    and 1-D-mesh paths are special cases of the same placement.

>>> from repro.sharding.specs import cohort_pspec, group_param_pspec
>>> cohort_pspec(2, data_axes=("data",))          # (K, max_n) client batch
PartitionSpec('data', None)
>>> group_param_pspec((3, 16, 10), model_size=2)  # m-stacked (m, d, C) leaf
PartitionSpec(None, 'model', None)
>>> group_param_pspec((3, 16, 10), model_size=1)  # model axis 1: replicate
PartitionSpec(None, None, None)

Tensor-parallel scheme over the "model" mesh axis (size MP=16):
  embedding / lm_head        shard the (padded) vocab dim
  attention wq/wo            shard heads      (only if n_heads  % MP == 0)
  attention wk/wv            shard kv heads   (only if n_kv     % MP == 0)
  MLP w_gate/w_up/w_down     shard d_ff
  MoE expert stacks          shard the EXPERT axis (expert parallelism)
  MLA w_uq/w_uk/w_uv/wo      shard heads;  w_dq shards q_rank
  Mamba2 wz/wx/out_proj      shard d_inner;  B/C/dt stay replicated
  xLSTM                      replicated on "model" (4 heads < MP) — these
                             models are small; ZeRO handles their memory
  1-D params (norms, biases) replicated

Batch/data tensors shard over ("pod","data") when the batch dim divides the
axis product, else they are replicated (long_500k has B=1).

``zero=True`` additionally shards optimizer moments (and optionally params,
fsdp=True) over "data" along the largest already-unsharded dim that divides
— ZeRO-1/3 style memory scaling. This is a §Perf lever, off by default.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.zoo import ArchConfig

MP_AXIS = "model"


def _path_names(path):
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
    return out


def _rule(names: list[str], shape: tuple, cfg: ArchConfig, mp: int,
          moe_2d: bool = False) -> P:
    """PartitionSpec for one parameter leaf (without the stacked-layer dim —
    the caller prepends None for leaves living under 'blocks')."""
    name = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    nd = len(shape)
    rep = P(*([None] * nd))
    if nd <= 1:
        return rep

    heads_ok = cfg.n_heads % mp == 0
    kv_ok = cfg.n_kv_heads % mp == 0
    ff = cfg.moe_d_ff if (cfg.family == "moe" and parent != "shared") else cfg.d_ff
    ff_ok = ff % mp == 0 and ff > 0
    vocab_ok = cfg.padded_vocab % mp == 0
    di_ok = (cfg.ssm_expand * cfg.d_model) % mp == 0

    if name == "embed":
        return P(MP_AXIS, None) if vocab_ok else rep
    if name == "lm_head":
        return P(None, MP_AXIS) if vocab_ok else rep
    if name in ("frontend_proj",):
        return rep
    if parent == "projector":
        return rep

    if parent == "attn" or parent == "shared_attn":
        if name in ("wq",):
            return P(None, MP_AXIS) if heads_ok else rep
        if name in ("wk", "wv"):
            return P(None, MP_AXIS) if kv_ok else rep
        if name == "wo":
            return P(MP_AXIS, None) if heads_ok else rep
        # MLA projections
        if name == "w_dq":
            return P(None, MP_AXIS) if cfg.q_rank % mp == 0 else rep
        if name == "w_uq":
            return (P(MP_AXIS, None) if cfg.q_rank % mp == 0
                    else (P(None, MP_AXIS) if heads_ok else rep))
        if name in ("w_uk", "w_uv"):
            return P(None, MP_AXIS) if heads_ok else rep
        if name == "w_dkv":
            return rep
    if parent == "mlp" or parent == "shared":
        if name in ("w_gate", "w_up"):
            return P(None, MP_AXIS) if ff_ok else rep
        if name == "w_down":
            return P(MP_AXIS, None) if ff_ok else rep
    if parent == "moe":
        if name == "router":
            return rep
        if name in ("w_gate", "w_up", "w_down") and nd == 3:
            if moe_2d and cfg.n_experts % (mp * mp) == 0:
                # 2-D expert parallelism: experts over BOTH axes -> weights
                # never gathered; tokens move via all-to-all (§Perf)
                return P(("data", MP_AXIS), None, None)
            return (P(MP_AXIS, None, None) if cfg.n_experts % mp == 0 else rep)
    if parent == "mixer":
        if name in ("wz", "wx"):
            return P(None, MP_AXIS) if di_ok else rep
        if name == "out_proj":
            return P(MP_AXIS, None) if di_ok else rep
        if name == "conv_x":
            return P(None, MP_AXIS) if di_ok else rep
        return rep
    # xLSTM / leftovers: replicate
    return rep


def param_specs(params, cfg: ArchConfig, mp: int = 16,
                fsdp_axis: Optional[str] = None, moe_2d: bool = False):
    """Pytree of PartitionSpec matching ``params``.

    fsdp_axis: if set (e.g. "data"), additionally shard each leaf's largest
    not-yet-sharded divisible dim over that axis (ZeRO-3 / FSDP).
    moe_2d: shard MoE expert stacks over BOTH mesh axes (expert parallelism
    across the full chip count — weights stay put, tokens all-to-all).
    """
    def spec_for(path, leaf):
        names = _path_names(path)
        stacked = ("blocks" in names) or (names and names[0] == "blocks_list")
        shape = leaf.shape[1:] if stacked and leaf.ndim >= 1 else leaf.shape
        base = _rule(names, shape, cfg, mp, moe_2d=moe_2d)
        parts = ([None] + list(base)) if stacked else list(base)
        if fsdp_axis is not None and leaf.ndim >= 2:
            parts = _add_fsdp(parts, leaf.shape, fsdp_axis)
        return P(*parts)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def _add_fsdp(parts, shape, axis, axis_size: int = 16):
    """Shard the largest unsharded, divisible dim over ``axis``."""
    used = set()
    for p in parts:
        for a in (p if isinstance(p, tuple) else (p,)):
            used.add(a)
    if axis in used:
        return parts          # axis already consumed by this leaf's spec
    best, best_dim = -1, -1
    for i, (p, d) in enumerate(zip(parts, shape)):
        if p is None and d % axis_size == 0 and d > best:
            best, best_dim = d, i
    if best_dim >= 0:
        parts = list(parts)
        parts[best_dim] = axis
    return parts


def state_specs(state_template, cfg: ArchConfig, mp: int = 16,
                zero: bool = False, fsdp: bool = False, moe_2d: bool = False):
    """Specs for the full train state {params, mu, nu, step}."""
    p_specs = param_specs(state_template["params"], cfg, mp,
                          fsdp_axis="data" if fsdp else None, moe_2d=moe_2d)
    m_specs = param_specs(state_template["mu"], cfg, mp,
                          fsdp_axis="data" if (zero or fsdp) else None,
                          moe_2d=moe_2d)
    return {"params": p_specs, "mu": m_specs, "nu": m_specs, "step": P()}


# ---------------------------------------------------------------------------
# Federated round executor (fed.parallel) — 2-D (data, model) placement
# ---------------------------------------------------------------------------

def data_axis_names(mesh) -> tuple:
    """The mesh axes the client (cohort) axis shards over: the data-ish
    axes ("pod", "data") when present, every axis of a mesh that has
    neither (the legacy 1-D case)."""
    named = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return named or tuple(mesh.axis_names)


def _data_entry(data_axes):
    """One PartitionSpec entry over the data axes: the bare axis name for a
    single axis (the form ``PartitionSpec`` normalizes a 1-tuple to), the
    tuple for several."""
    data_axes = tuple(data_axes)
    return data_axes[0] if len(data_axes) == 1 else data_axes


def cohort_pspec(ndim: int, data_axes=("data",)) -> P:
    """Spec for one K-leading cohort leaf (X/Y/n/keys/assignment state):
    client axis sharded over the data axes, everything else replicated."""
    return P(_data_entry(data_axes), *([None] * (ndim - 1)))


def block_staged_pspec(ndim: int, data_axes=("data",)) -> P:
    """Spec for one staged round-block leaf (cohort ids / solver keys /
    alive mask of shape ``(B, K, ...)``): the scan (round) axis stays
    replicated — every device steps through all B rounds — and the client
    axis (axis 1) shards over the data axes, i.e. ``cohort_pspec`` shifted
    one axis right.

    >>> from repro.sharding.specs import block_staged_pspec
    >>> block_staged_pspec(2, data_axes=("data",))   # (B, K) cohort ids
    PartitionSpec(None, 'data')
    >>> block_staged_pspec(2, data_axes=("pod", "data"))
    PartitionSpec(None, ('pod', 'data'))
    """
    return P(None, _data_entry(data_axes), *([None] * (ndim - 2)))


def group_param_pspec(shape: tuple, model_size: int,
                      model_axis: str = MP_AXIS) -> P:
    """Spec for one m-stacked group-parameter leaf.

    The leading (group) axis stays replicated — every device owns all m
    group models, exactly like the 1-D path — and the *largest* trailing
    dim divisible by ``model_size`` shards over "model" (the local solver's
    parameter axis). No divisible dim, or ``model_size == 1``, degrades to
    full replication: the 1-device and 1-D-mesh placements are the
    ``model_size == 1`` special case.
    """
    nd = len(shape)
    parts = [None] * nd
    if model_size > 1 and nd >= 2:
        best, best_dim = -1, -1
        for i in range(1, nd):
            if shape[i] % model_size == 0 and shape[i] > best:
                best, best_dim = shape[i], i
        if best_dim >= 0:
            parts[best_dim] = model_axis
    return P(*parts)


def group_param_specs(group_params, mesh) -> object:
    """Pytree of ``group_param_pspec`` for an m-stacked parameter pytree
    under ``mesh`` (model-axis size read off the mesh; 1 when absent)."""
    model_size = dict(mesh.shape).get(MP_AXIS, 1)
    return jax.tree_util.tree_map(
        lambda l: group_param_pspec(tuple(l.shape), model_size), group_params)


# ---------------------------------------------------------------------------
# Data tensors
# ---------------------------------------------------------------------------

def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_specs(batch_tree, mesh, include_model: bool = False):
    """Shard the leading batch dim over ("pod","data") when divisible.

    include_model (§Perf): for architectures with NO tensor-parallel
    parameters (e.g. xLSTM: 4 heads < 16-way model axis, everything
    replicated) the "model" axis is idle — shard the batch over it too,
    dividing activation memory by the model-axis size for free.
    """
    axes = batch_axes(mesh)
    if include_model:
        axes = axes + (MP_AXIS,)
    total = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1

    def spec(leaf):
        if leaf.ndim == 0:
            return P()
        if leaf.shape[0] % total == 0 and leaf.shape[0] > 0:
            return P(axes, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map(spec, batch_tree)


def cache_specs(cache_tree, cfg: ArchConfig, mesh, mp: int = 16,
                seq_shard: bool = False):
    """Decode-cache sharding: batch dim over data axes; head/expert-ish dims
    over "model" where divisible. Cache layouts (leading L = stacked layers):
      attn k/v   (L, B, S, KV, hd)
      mla        c_kv (L, B, S, r) / k_pe (L, B, S, rope)
      mamba      conv_* (L, B, W-1, C) / ssm (L, B, H, P, N)
      xlstm      per-layer lists of small states

    seq_shard (§Perf optimization): when the kv-head dim does NOT divide the
    model axis (kv < 16), shard the cache's SEQUENCE dim over "model"
    instead of replicating. Attention over a seq-sharded cache only needs
    softmax-stat all-reduces (bytes ~ B·H), eliminating the full-cache
    all-gather XLA otherwise inserts to re-lay-out the loop-carried cache.
    """
    axes = batch_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1

    def spec_for(path, leaf):
        names = _path_names(path)
        nd = leaf.ndim
        parts = [None] * nd
        stacked = nd >= 1 and any(n in ("k", "v", "c_kv", "k_pe", "conv_x",
                                        "conv_B", "conv_C", "ssm")
                                  for n in names)
        # batch dim position: 1 for stacked layer caches, 0 for xlstm lists
        bdim = 1 if (stacked and names[0] != "xlstm") else 0
        if nd > bdim and leaf.shape[bdim] % total == 0:
            parts[bdim] = axes
        # model-axis dims
        last = names[-1]
        if last in ("k", "v") and nd == 5:
            if cfg.n_kv_heads % mp == 0:
                parts[3] = MP_AXIS
            elif seq_shard and leaf.shape[2] % mp == 0:
                parts[2] = MP_AXIS
        if last == "c_kv" and nd == 4:
            if seq_shard and leaf.shape[2] % mp == 0:
                parts[2] = MP_AXIS           # MLA latent: seq dim
            elif cfg.kv_rank % mp == 0:
                parts[3] = MP_AXIS
        if last == "k_pe" and nd == 4 and seq_shard and leaf.shape[2] % mp == 0:
            parts[2] = MP_AXIS
        if last == "ssm" and nd == 5:
            H = leaf.shape[2]
            if H % mp == 0:
                parts[2] = MP_AXIS
        if last in ("conv_x",) and nd == 4 and leaf.shape[3] % mp == 0:
            parts[3] = MP_AXIS
        return P(*parts)

    return jax.tree_util.tree_map_with_path(spec_for, cache_tree)
