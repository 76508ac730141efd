"""The plain reference against ``FedGroupTrainer.round`` at a CPU size,
pinned and streamed (with eq.-9 newcomers), and the lower-precision
controls that the comparison has to refuse."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), os.path.dirname(__file__)]

from bench import compare, harness  # noqa: E402
from bench import generators as gen  # noqa: E402
import tiny_cell  # noqa: E402

SEED = 2_147_483_659


def _program(parts, data):
    tr = harness.build_trainer(parts["config"], parts["traffic"], SEED, data)
    feed = harness.Feed(tr)
    tr.group_cold_start()
    start, prog, cohorts = harness.check_rounds(tr, feed, data, 3)
    ids = harness.eval_ids(tr)
    tr.close()
    return start, prog, cohorts, ids


@pytest.mark.parametrize("share,streamed", [(1.0, False), (0.25, False),
                                            (1.0, True)])
def test_reference_agrees_with_the_program(share, streamed):
    """At a cold-start share of 0.25 only a quarter of the pinned clients
    are assigned before the check rounds; a streamed population brings
    arrivals every round. Either way cohorts hold newcomers that take
    eq. 9."""
    parts = tiny_cell.parts(share, streamed)
    data = gen.make_data(SEED, parts["config"], parts["traffic"])
    start, prog, cohorts, ids = _program(parts, data)
    newcomers = sum(int((start["membership"][c] < 0).sum()) for c in cohorts)
    assert (newcomers > 0) == (share < 1.0 or streamed)
    ref = harness.reference_rounds(data, parts["config"], start, cohorts,
                                   prog, ids)
    nums = compare.numbers(start, prog, ref)
    # eq. 9 placed every newcomer in the reference's least dissimilar group
    assert ("assign_gap" in nums) == (newcomers > 0)
    assert nums.get("assign_gap", 0.0) < 1e-6
    assert nums["loss_gap"] < 1e-6 and nums["update_gap"] < 1e-6
    assert nums["change_gap"] < 1e-6 and nums["acc_gap"] == 0.0
    assert compare.verdict(nums, parts["limits"])[0]


def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


@pytest.mark.parametrize("control", ["reference_in_bf16",
                                     "program_on_bf16_data_and_weights"])
def test_lower_precision_fails(control):
    parts = tiny_cell.parts()
    config = parts["config"]
    data = gen.make_data(SEED, config, parts["traffic"])
    if control == "reference_in_bf16":
        start, prog, cohorts, ids = _program(parts, data)
        low = harness.reference_rounds(data, config, start, cohorts, prog,
                                       ids, dtype=jnp.bfloat16)
        for a, p in zip(low, prog):
            a["n_test"] = p["n_test"]
        prog = low
    else:
        low = dict(data, x_train=_bf16(data["x_train"]),
                   x_test=_bf16(data["x_test"]))
        tr = harness.build_trainer(parts["config"], parts["traffic"], SEED,
                                   low)
        feed = harness.Feed(tr)
        tr.group_cold_start()
        tr.group_params = jax.tree_util.tree_map(
            lambda v: jnp.asarray(_bf16(v)), tr.group_params)
        # the reference starts from the same rounded weights and keeps
        # the data at full precision
        start, prog, cohorts = harness.check_rounds(tr, feed, low, 3)
        ids = harness.eval_ids(tr)
        tr.close()
    ref = harness.reference_rounds(data, config, start, cohorts, prog, ids)
    ok, checks = compare.verdict(compare.numbers(start, prog, ref),
                                 parts["limits"])
    assert not ok, checks


def test_a_number_with_no_limit_is_shown_and_does_not_decide():
    nums = {"loss_gap": 0.001, "acc_gap": 0.5}
    ok, checks = compare.verdict(nums, {"loss_gap": 0.01})
    assert ok and checks["acc_gap"] == {"value": 0.5, "limit": None}
    ok, checks = compare.verdict(nums, {"loss_gap": 0.0001})
    assert not ok and checks["loss_gap"]["limit"] == 0.0001
