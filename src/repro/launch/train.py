"""Training launcher.

Two modes:
  --mode fed   (default) federated training with any framework on the
               synthetic federated datasets — the paper's workload.
  --mode lm    language-model training of a zoo architecture (reduced or
               full config) on synthetic token data — the substrate driver
               used by examples/zoo_train.py.

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode fed \
      --framework fedgroup --dataset femnist --rounds 30
  PYTHONPATH=src python -m repro.launch.train --mode lm \
      --arch gemma-2b --smoke --steps 200
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import save_pytree


def run_fed(args) -> int:
    from repro.core.fedgroup import FedGrouProxTrainer, FedGroupTrainer
    from repro.data import generators as gen
    from repro.fed.engine import FedAvgTrainer, FedConfig, FedProxTrainer
    from repro.fed.fesem import FeSEMTrainer
    from repro.fed.ifca import IFCATrainer
    from repro.models.paper_models import lstm_classifier, mclr, mlp

    datasets = {
        "mnist": lambda: (gen.mnist_like(args.seed, n_clients=args.clients or 1000,
                                         classes_per_client=2,
                                         total_train=20000, dim=128),
                          mclr(128, 10)),
        "mnist_mlp": lambda: (gen.mnist_like(args.seed, n_clients=args.clients or 1000,
                                             classes_per_client=2,
                                             total_train=20000, dim=128),
                              mlp(128, 128, 10)),
        "femnist": lambda: (gen.femnist_like(args.seed,
                                             n_clients=args.clients or 200,
                                             total_train=15000, dim=128),
                            mlp(128, 128, 62)),
        "synthetic": lambda: (gen.synthetic(1.0, 1.0, args.seed,
                                            n_clients=args.clients or 100),
                              mclr(60, 10)),
        "sent140": lambda: (gen.sent140_like(args.seed,
                                             n_clients=args.clients or 300,
                                             total_train=10000, vocab=400),
                            lstm_classifier(400, 16, 32)),
    }
    frameworks = {
        "fedavg": FedAvgTrainer, "fedprox": FedProxTrainer,
        "fedgroup": FedGroupTrainer, "fedgrouprox": FedGrouProxTrainer,
        "ifca": IFCATrainer, "fesem": FeSEMTrainer,
    }
    data, model = datasets[args.dataset]()
    cfg = FedConfig(n_rounds=args.rounds, clients_per_round=args.k,
                    local_epochs=args.epochs, batch_size=args.batch,
                    lr=args.lr, mu=args.mu, n_groups=args.groups,
                    pretrain_scale=args.alpha, eta_g=args.eta_g,
                    measure=args.measure, seed=args.seed,
                    async_depth=args.async_depth,
                    async_alpha=args.async_alpha,
                    async_beta=args.async_beta,
                    telemetry_dir=args.telemetry_dir)
    tr = frameworks[args.framework](model, data, cfg)
    print(f"# {args.framework} on {data.name}: {data.n_clients} clients, "
          f"m={cfg.n_groups}, K={cfg.clients_per_round}, E={cfg.local_epochs}"
          + (f", async_depth={cfg.async_depth}" if cfg.async_depth else ""))
    t0 = time.time()
    if cfg.async_depth:
        # async mode folds FIFO inside run(); report per-fold metrics after
        tr.run(cfg.n_rounds)
        for t, m in enumerate(tr.history.rounds):
            print(f"round {t:3d} acc={m.weighted_acc:.4f} "
                  f"disc={m.discrepancy:.4f}")
        st = tr.history.async_stats
        print(f"async: folds={st.get('folds')} "
              f"max_in_flight={st.get('max_in_flight')} "
              f"staleness={st.get('staleness_hist')} ({time.time()-t0:.1f}s)")
    else:
        for t in range(cfg.n_rounds):
            m = tr.round(t)
            print(f"round {t:3d} acc={m.weighted_acc:.4f} "
                  f"disc={m.discrepancy:.4f} ({time.time()-t0:.1f}s)")
    print(f"max_acc={tr.history.max_acc:.4f}")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        from repro.fed.server import tree_index
        params = (tree_index(tr.group_params, 0)
                  if hasattr(tr, "group_params") else tr.params)
        save_pytree(os.path.join(args.out, "model.npz"), params,
                    {"framework": args.framework, "dataset": args.dataset,
                     "max_acc": tr.history.max_acc})
        with open(os.path.join(args.out, "history.json"), "w") as f:
            json.dump([r.__dict__ for r in tr.history.rounds], f, indent=1)
        print(f"saved to {args.out}")
    tr.close()          # flush telemetry (trace.json + run_summary.json)
    if args.telemetry_dir:
        print(f"telemetry in {args.telemetry_dir} — render with "
              f"python -m repro.launch.inspect {args.telemetry_dir}")
    return 0


def run_lm(args) -> int:
    from repro.configs import registry
    from repro.models import zoo

    cfg = registry.get(args.arch)
    if args.smoke:
        cfg = registry.smoke_variant(cfg)
    key = jax.random.PRNGKey(args.seed)
    state = zoo.init_train_state(key, cfg)
    from repro.models.modules import param_count
    print(f"# LM training {cfg.name} ({'smoke' if args.smoke else 'full'}): "
          f"{param_count(state['params']):,} params")

    B, S = args.batch, args.seq
    step_fn = jax.jit(lambda st, b: zoo.train_step(st, b, cfg))

    def make_batch(k):
        # synthetic markovian token stream: learnable bigram structure
        trans = jax.random.categorical(
            jax.random.PRNGKey(7), jnp.zeros((cfg.vocab_size, 32)), axis=-1)
        toks = jax.random.randint(k, (B, S + 1), 0, cfg.vocab_size)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    t0 = time.time()
    for step in range(args.steps):
        key, sk = jax.random.split(key)
        state, metrics = step_fn(state, make_batch(sk))
        if step % max(args.steps // 10, 1) == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss={float(metrics['loss']):.4f} "
                  f"({time.time()-t0:.1f}s)")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        save_pytree(os.path.join(args.out, "state.npz"), state,
                    {"arch": cfg.name, "steps": args.steps})
        print(f"saved to {args.out}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("fed", "lm"), default="fed")
    # fed args
    ap.add_argument("--framework", default="fedgroup")
    ap.add_argument("--dataset", default="mnist")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--batch", type=int, default=10)
    ap.add_argument("--lr", type=float, default=0.03)
    ap.add_argument("--mu", type=float, default=0.0)
    ap.add_argument("--groups", type=int, default=3)
    ap.add_argument("--alpha", type=int, default=20)
    ap.add_argument("--eta-g", type=float, default=0.0, dest="eta_g")
    ap.add_argument("--measure", choices=("edc", "madc"), default="edc")
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--async-depth", type=int, default=0, dest="async_depth",
                    help="D>0 keeps D in-flight cohort dispatches, folded "
                         "with FedAsync staleness weights (0 = synchronous)")
    ap.add_argument("--async-alpha", type=float, default=1.0,
                    dest="async_alpha")
    ap.add_argument("--telemetry-dir", default=None, dest="telemetry_dir",
                    help="stream spans/metrics here; render with "
                         "python -m repro.launch.inspect DIR")
    ap.add_argument("--async-beta", type=float, default=0.0,
                    dest="async_beta")
    # lm args
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    # common
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return run_fed(args) if args.mode == "fed" else run_lm(args)


if __name__ == "__main__":
    sys.exit(main())
