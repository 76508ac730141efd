"""A cell small enough for a CPU test run: the femnist-like generator and
an MLP at toy widths, through the same harness as the chip cells."""
import copy

import pytest

CONFIG = {
    "name": "tiny",
    "data": {"generator": "femnist_like", "n_clients": 12,
             "total_samples": 400, "dim": 16, "n_classes": 5,
             "n_styles": 2, "writer_classes": [2, 4], "min_size": 10,
             "max_size": 60, "size_alpha": 1.5, "test_share": 0.2},
    "model": {"kind": "mlp", "in_dim": 16, "hidden": 8, "n_classes": 5,
              "d_w": 17 * 8 + 9 * 5},
    "fed": {"framework": "fedgroup", "measure": "edc", "local_epochs": 2,
            "batch_size": 4, "lr": 0.05, "n_groups": 3, "eta_g": 0.0},
}
TRAFFIC = {"name": "tiny", "feeding": "pinned", "clients_per_round": 4,
           "cold_start_share": 1.0, "eval_every": 1, "check_rounds": 3,
           "trace_seconds": 1}
# a streamed population: 60 clients, 12 active at the start, about two
# arrivals a round, each a newcomer that takes eq. 9 in its first cohort
STREAMED = {"name": "tiny_streamed", "feeding": "population",
            "population": 60, "initial_active": 12, "arrival_rate": 2,
            "newcomers_join": True, "prefetch": 2, "eval_every": 2,
            "eval_clients": 30, "eval_warm_clients": 4,
            "clients_per_round": 4, "cold_start_share": 1.0,
            "check_rounds": 3, "trace_seconds": 1}
MNIST = {"generator": "mnist_like", "n_clients": 12, "total_samples": 400,
         "dim": 16, "n_classes": 5, "classes_per_client": 2,
         "min_size": 10, "max_size": 60, "size_alpha": 1.5,
         "test_share": 0.2}
# float32 on the CPU agrees with the reference to ~1e-7 (loss, update and
# change gaps) and exactly in the eval counts; a bfloat16 pass is off by
# 1e-3 or more. The limits sit between.
LIMITS = {"loss_gap": 1e-5, "update_gap": 1e-5, "change_gap": 1e-5,
          "assign_gap": 1e-5,
          "acc_gap": None}


def parts(cold_start_share: float = 1.0, streamed: bool = False) -> dict:
    config = copy.deepcopy(CONFIG)
    if streamed:
        config["data"] = dict(MNIST)
        traffic = dict(STREAMED, cold_start_share=cold_start_share)
    else:
        traffic = dict(TRAFFIC, cold_start_share=cold_start_share)
    return {"cell": {"name": "tiny", "chips": 1},
            "config": config, "traffic": traffic,
            "limits": dict(LIMITS),
            "end_to_end": ["client_updates_per_s", "round_ms_p95",
                           "setup_s"],
            "per_layer": []}


@pytest.fixture
def jax_config_restored():
    """``harness.run`` points JAX's persistent cache at the checkout for
    its process; a test that drives it puts the settings back after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()
