"""Client-side local optimization (Algorithm 1 ClientUpdate + FedProx variant).

The solver is built once per (model, hyperparams) and vmapped over a client
axis — on a TPU mesh that axis is sharded over "data" (see fed/parallel.py),
which is the TPU-native replacement for the paper's sequential client loop.

Every client's data is padded to a fixed max size; batches are drawn
uniformly from the valid prefix. The number of SGD steps is
``E * ceil(n_i / B)`` (per the paper: E local epochs of mini-batch SGD),
masked inside a fixed-trip-count ``fori_loop`` so one compiled program serves
all client sizes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models.paper_models import ModelSpec


def make_local_solver(model: ModelSpec, *, epochs: int, batch_size: int,
                      lr: float, mu: float = 0.0, max_samples: int):
    """Returns solve(params0, x, y, n_valid, key) -> (delta, final_params)."""
    max_steps = epochs * ((max_samples + batch_size - 1) // batch_size)

    def loss_with_prox(params, params0, xb, yb):
        l = model.loss(params, {"x": xb, "y": yb})
        if mu > 0.0:
            sq = sum(jnp.sum(jnp.square(p - p0)) for p, p0 in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(params0)))
            l = l + 0.5 * mu * sq
        return l

    grad_fn = jax.grad(loss_with_prox)

    def solve(params0, x, y, n_valid, key):
        n_valid = jnp.maximum(n_valid, 1)
        steps = epochs * ((n_valid + batch_size - 1) // batch_size)

        def body(i, carry):
            params, key = carry
            key, sk = jax.random.split(key)
            idx = jax.random.randint(sk, (batch_size,), 0, n_valid)
            g = grad_fn(params, params0, x[idx], y[idx])
            live = (i < steps).astype(jnp.float32)
            params = jax.tree_util.tree_map(
                lambda p, gg: p - lr * live * gg, params, g)
            return params, key

        params, _ = jax.lax.fori_loop(0, max_steps, body, (params0, key))
        delta = jax.tree_util.tree_map(lambda a, b: a - b, params, params0)
        return delta, params

    return solve


def make_batch_solver(model: ModelSpec, *, epochs: int, batch_size: int,
                      lr: float, mu: float = 0.0, max_samples: int):
    """vmapped + jitted solver over a stacked client axis.

    solve_many(params0, X (K,max_n,...), Y (K,max_n), n (K,), keys (K,2))
      -> (deltas stacked over clients, final params stacked)
    """
    solve = make_local_solver(model, epochs=epochs, batch_size=batch_size,
                              lr=lr, mu=mu, max_samples=max_samples)
    return jax.jit(jax.vmap(solve, in_axes=(None, 0, 0, 0, 0)))


def _correct_one(model: ModelSpec):
    """Per-client correct-prediction count (params, x, y, n_valid) -> int32.

    The forward pass runs at full f32 matmul precision. At a TPU's default
    precision the logits are rounded through bf16, and how they round — so
    which class wins the argmax — depends on how XLA fuses the surrounding
    program: the standalone eval and the eval inside a scanned round block
    counted different correct predictions for the same parameters."""
    def one(params, x, y, n_valid):
        with jax.default_matmul_precision("highest"):
            logits = model.apply(params, x)
        pred = jnp.argmax(logits, -1)
        ok = (pred == y) & (jnp.arange(y.shape[0]) < n_valid)
        return jnp.sum(ok)
    return one


def make_eval_fn(model: ModelSpec):
    """correct_counts(params, X (K,max_n,...), Y, n) -> (correct (K,), n)."""
    return jax.jit(jax.vmap(_correct_one(model), in_axes=(None, 0, 0, 0)))


def grouped_eval_correct(model: ModelSpec):
    """Un-jitted fused grouped-eval core: ONE program for all m groups.

    fn(group_params, membership, Xt, Yt, nt) -> (correct, total) int32
    scalars. group_params is the m-stacked pytree; membership (N,) routes
    each client's test shard to its group's model (-1 = never assigned,
    excluded from both counts) — the paper's §5.1 weighted accuracy as a
    single dispatch regardless of m, replacing the per-group eval loop
    (m dispatches + host accumulation). Each client gathers its own
    group's parameters (``g[membership]``, the round core's idiom) and is
    scored once — N forward passes total, same FLOPs as the retired loop,
    not m·N; the sums stay integer, so the host-side accuracy division is
    bit-identical to the retired loop's. Jit it at the call site (the
    trainers do); ``fed.rounds.make_block_executor`` runs it inside the
    scanned block at the ``eval_every`` cadence.
    """
    one = _correct_one(model)

    @jax.named_scope("grouped_eval")
    def fn(group_params, membership, Xt, Yt, nt):
        membership = membership.astype(jnp.int32)
        valid = membership >= 0
        m = jax.tree_util.tree_leaves(group_params)[0].shape[0]
        mem = jnp.clip(membership, 0, m - 1)
        my_params = jax.tree_util.tree_map(lambda g: g[mem], group_params)
        per_client = jax.vmap(one)(my_params, Xt, Yt, nt)   # (N,) int32
        correct = jnp.sum(jnp.where(valid, per_client, 0))
        total = jnp.sum(jnp.where(valid, nt.astype(jnp.int32), 0))
        return correct, total

    return fn


def client_mean_loss(model: ModelSpec):
    """Unjitted per-client mean CE loss (params, x, y, n_valid) -> scalar —
    the IFCA cluster-identity score, reused both by the standalone loss
    evaluator below and by the in-program assignment stage of the fused
    round (``fed.ifca.make_ifca_assign``)."""
    def one(params, x, y, n_valid):
        logits = model.apply(params, x)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        ce = -jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None], -1)[:, 0]
        mask = jnp.arange(y.shape[0]) < n_valid
        return jnp.sum(ce * mask) / jnp.maximum(n_valid, 1)
    return one


def make_loss_eval_fn(model: ModelSpec):
    """mean train loss per client (used by IFCA cluster estimation)."""
    return jax.jit(jax.vmap(client_mean_loss(model), in_axes=(None, 0, 0, 0)))
