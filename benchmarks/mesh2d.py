"""Benchmark: 2-D (data × model) mesh vs the 1-D data mesh round time.

The PR-4 tentpole claims the round executor generalizes to a 2-D
``(data, model)`` mesh with the 1-D path as a special case; this entry
keeps that claim measured. A subprocess with 4 forced host devices
(``--xla_force_host_platform_device_count``, the mesh cannot be built in
the already-initialized parent) times one compiled round of the static
executor at the framework-comparison scale (m=5 groups, K=50 clients) on

  * a (4,)      1-D "data" mesh          (the PR-2 path), and
  * a (2, 2)    (data, model) mesh       (the tentpole path),

interleaved (bench_io.interleaved_best) so the watched ratio
``mesh2d_ratio`` = 1-D time / 2-D time does not inherit host-load drift.
Metrics are appended to BENCH_round_exec.json (same file as the fused-vs-
serial trajectory — one place for all round-executor perf); the >2x
regression gate in benchmarks/run.py watches ``mesh2d_ratio``
(docs/benchmarks.md documents the schema and the gate semantics).

On a CPU host the model axis buys nothing (emulated collectives), so the
ratio is expected near or below 1; the gate only guards against the 2-D
lowering becoming catastrophically slower (a >2x drop from the committed
best), not for speedups that need real hardware.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.bench_io import record_run

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DRIVER = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np
from benchmarks.bench_io import interleaved_best
from repro.fed import parallel as fp
from repro.fed import rounds
from repro.launch.mesh import make_fed_mesh
from repro.models.paper_models import mclr

m, K, dim, max_n, epochs, batch, reps = (
    json.loads(__import__("sys").argv[1]))
model = mclr(dim, 10)
key = jax.random.PRNGKey(0)
params = model.init(key)
ks = jax.random.split(key, 4)
gp = jax.tree_util.tree_map(
    lambda l: jnp.stack([l + 0.01 * j for j in range(m)]), params)
X = jax.random.normal(ks[0], (K, max_n, dim))
Y = jax.random.randint(ks[1], (K, max_n), 0, 10)
n = jnp.full((K,), max_n, jnp.int32)
mem = jnp.asarray(np.arange(K) % m, jnp.int32)
keys = jax.random.split(ks[2], K)
fn = rounds.make_round_executor(model, epochs=epochs, batch_size=batch,
                                lr=0.05, mu=0.0, n_groups=m,
                                max_samples=max_n)
ex1 = fp.make_sharded_executor(fn, make_fed_mesh(4, 1))
ex2 = fp.make_sharded_executor(fn, make_fed_mesh(2, 2))
us1, us2 = interleaved_best(
    [lambda: jax.block_until_ready(ex1(gp, mem, X, Y, n, keys).group_params),
     lambda: jax.block_until_ready(ex2(gp, mem, X, Y, n, keys).group_params)],
    reps=reps)
print(json.dumps({"devices": jax.device_count(),
                  "mesh1d_us": us1, "mesh2d_us": us2}))
"""


def main(quick: bool = False, *, m: int = 5, K: int = 50):
    reps = 5 if quick else 10
    args = json.dumps([m, K, 32, 20, 2, 10, reps])
    env = dict(os.environ)
    # the child measures the CPU emulation of the mesh by design, and must
    # not reach for a chip the parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, os.path.join(_REPO, "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run([sys.executable, "-c", _DRIVER, args], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh2d driver failed: {proc.stderr[-1500:]}")
    timed = json.loads(proc.stdout.strip().splitlines()[-1])

    metrics = {"quick": quick, "m": m, "K": K,
               "mesh1d_us": timed["mesh1d_us"],
               "mesh2d_us": timed["mesh2d_us"],
               "mesh2d_ratio": timed["mesh1d_us"] /
               max(timed["mesh2d_us"], 1e-9)}
    print(f"\n# 2-D mesh (m={m}, K={K}, 4 forced host devices): "
          f"1-D (4,1) {metrics['mesh1d_us']:.0f}us vs "
          f"2-D (2,2) {metrics['mesh2d_us']:.0f}us -> "
          f"mesh2d_ratio={metrics['mesh2d_ratio']:.2f}x")
    regression, details = record_run(
        "BENCH_round_exec.json", metrics, watch=[("mesh2d_ratio", "min")])
    if regression:
        print("REGRESSION:", "; ".join(details),
              "(gate semantics: docs/benchmarks.md)")
    return {"mesh2d_ratio": round(metrics["mesh2d_ratio"], 2),
            "regression": regression, "regression_details": details,
            **metrics}


if __name__ == "__main__":
    sys.exit(0 if not main(quick="--quick" in sys.argv).get("regression")
             else 1)
