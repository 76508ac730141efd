"""The numbers that decide ``correct``: the program's check rounds against
the reference's.

A check round's reading is a dict with the group models ``groups`` (an
m-stacked parameter pytree), the auxiliary global model ``glob``, the
round's mean local loss ``loss`` and its correct test predictions
``correct``. ``start`` is the state both sides started from. The
numbers:

  loss_gap    max over the check rounds of |loss - ref| / |ref|
  update_gap  the first round's change of every leaf (each group's and the
              global model's), by its norm: the worst leaf's
              |norm - ref norm| / max(ref norm, median ref norm)
  change_gap  the same for the change over all the check rounds
  assign_gap  max over the check rounds' newcomers of how far the eq.-9
              dissimilarity of the group the program chose lies above the
              least (``Reference.cold_assign``)
  acc_gap     max over the check rounds that evaluated of
              |correct - ref| / the test samples evaluated

A leaf whose reference change is under a thousandth of the median leaf's
(a group that no cohort client joined) moves by round-off alone, and is
left out of update_gap and change_gap.
"""
from __future__ import annotations

import jax
import numpy as np

NEGLIGIBLE = 1e-3


def _changes(after, before):
    """(path, change) of every leaf of a parameter pytree, in float64."""
    leaves = jax.tree_util.tree_flatten_with_path(after)[0]
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(before)):
        yield (jax.tree_util.keystr(path),
               np.asarray(a, np.float64) - np.asarray(b, np.float64))


def leaf_norms(after: dict, before: dict) -> dict:
    """Norm of every leaf's change, per group and for the global model,
    named by the leaf's path in the parameter pytree."""
    out = {}
    for key, d in _changes(after["groups"], before["groups"]):
        for j in range(d.shape[0]):
            out[f"group{j}{key}"] = float(np.linalg.norm(d[j]))
    for key, d in _changes(after["glob"], before["glob"]):
        out[f"global{key}"] = float(np.linalg.norm(d))
    return out


def norm_gap(prog: dict, ref: dict) -> float:
    """Worst leaf's gap between the program's and the reference's change
    norm, against the larger of its reference norm and the median's."""
    med = float(np.median(list(ref.values())))
    gaps = [abs(prog[k] - r) / max(r, med) for k, r in ref.items()
            if r >= NEGLIGIBLE * med]
    return max(gaps) if gaps else 0.0


def numbers(start: dict, prog: list, ref: list) -> dict:
    """The compared numbers of one run (see the module docstring);
    acc_gap only where a check round evaluated."""
    out = {
        "loss_gap": float(max(abs(p["loss"] - r["loss"]) / abs(r["loss"])
                              for p, r in zip(prog, ref))),
        "update_gap": norm_gap(leaf_norms(prog[0], start),
                               leaf_norms(ref[0], start)),
        "change_gap": norm_gap(leaf_norms(prog[-1], start),
                               leaf_norms(ref[-1], start)),
    }
    gaps = [g for r in ref for g in r["assign_gaps"]]
    if gaps:
        out["assign_gap"] = max(gaps)
    evals = [(p, r) for p, r in zip(prog, ref) if p["correct"] is not None]
    if evals:
        out["acc_gap"] = max(abs(p["correct"] - r["correct"])
                             / max(p["n_test"], 1) for p, r in evals)
    return out


def verdict(nums: dict, limits: dict) -> tuple:
    """-> (correct, checks): every limited number within its limit, and
    each number beside its limit. A number whose limit is None, or that
    has no limit, is shown and does not decide."""
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in nums.items()}
    ok = all(np.isfinite(v) for v in nums.values()) and all(
        c["value"] <= c["limit"] for c in checks.values()
        if c["limit"] is not None)
    return bool(ok), checks
