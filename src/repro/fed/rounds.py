"""Shared single-dispatch round executor (Algorithm 2 hot path).

Every framework round is one device dispatch: group parameters live as a
pytree stacked with leading axis ``m``; each selected client gathers its
group's parameters, the local solver runs vmapped over the client axis, and
per-group aggregation is a segment-sum (one-hot matmul). Inter-group
aggregation (η_G, Alg. 2 lines 17-19), the auxiliary global model, the
flattened per-group update directions, and the discrepancy metric (eq. 4)
are all fused into the same program, so

  * ``FedAvgTrainer`` / ``FedProxTrainer`` run it with m=1,
  * ``FedGroupTrainer`` / ``FedGrouProxTrainer`` with m=n_groups,
  * ``IFCATrainer`` / ``FeSEMTrainer`` with m=n_groups plus an in-program
    *assignment stage* (``assign_fn``): IFCA's per-client argmin-loss over
    all m stacked models and FeSEM's argmin-ℓ2 E-step over flattened
    centers run inside the same compiled round, feeding the gather /
    segment-sum directly — no host-side ``np.where`` loops or per-group
    solver launches even for the frameworks that reschedule every round
    (IFCA's m× model broadcast *accounting* is unchanged by the fusion:
    the server still ships all m models per round, we just price it
    without also paying m dispatches), and
  * ``fed.parallel.make_parallel_round`` re-exports it for the mesh path;
    the serial trainers shard the client axis over the mesh's data axes
    through ``fed.parallel.make_sharded_executor`` whenever more than one
    device is visible, and a 2-D ``(data, model)`` mesh additionally
    shards the local solver's parameter dim over "model"
    (``sharding.specs.group_param_pspec``; plain jit is the 1-device
    special case and replication the model-axis-1 one — docs/scaling.md)

— one compiled round instead of the seed's ``m`` solver launches plus a
dozen host-synchronizing aggregation dispatches per round.

``make_block_executor`` goes one step further: it wraps the same fused
round in a ``jax.lax.scan`` over B rounds, so B rounds cost ONE dispatch.
Host-side cohort selection never depends on device results, so the trainer
stages a ``(B, K)`` cohort index matrix, ``(B, K, 2)`` solver keys and a
``(B, K)`` zero-weight ``alive`` mask (``dropout_rate`` cohorts pad to K so
the scan shapes stay static) up front; client batches are gathered
in-program from the pinned stacks, the carry (m-stacked group params +
each framework's assignment state) is *donated* so group state updates in
place, and per-round metrics — including the fused grouped eval — come
back stacked ``(B,)`` and are fetched once per block. The per-round
``make_round_executor`` path survives unchanged as the equivalence oracle
and the streamed-population fallback (``fed.engine.run`` breaks blocks on
events that need the host: group cold start, cold newcomers in a cohort,
population streaming).

``serial_reference_round`` keeps the seed per-group loop alive as the
equivalence oracle for tests and the BENCH_round_exec baseline;
``serial_ifca_round`` / ``serial_fesem_round`` do the same for the retired
estimate-then-loop baselines of the dynamic-assignment frameworks.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed import client as client_lib
from repro.fed import server as server_lib
from repro.models.modules import flatten_updates


class RoundOutput(NamedTuple):
    group_params: object      # pytree stacked over m: post-η_G group models
    global_params: object     # auxiliary global model (mean of groups)
    agg_delta: object         # pytree stacked over m: intra-group FedAvg Δ
    group_delta_flat: object  # (m, d_w) flattened w_g^{t+1} − w_g^t
    discrepancy: object       # scalar: mean_i ||w_i^final − w̃_{g(i)}||
    membership: object        # (K,) int32 group id used this round
    assign_state: object      # updated assignment-stage state (None if static)
    mean_loss: object = 0.0   # scalar: n_i-weighted mean local train loss
                              # of the clients' final local models
    n_quarantined: object = 0  # scalar int32: alive clients whose updates
                               # were screened out this round


# 32-bit elements in one (8, 128) TPU tile
_TILE = 8 * 128


@partial(jax.tree_util.register_dataclass, data_fields=["x", "y", "n"],
         meta_fields=["x_rows", "y_rows"])
@dataclasses.dataclass(frozen=True)
class ClientStack:
    """The pinned per-client train stack, packed client-major.

    ``x`` and ``y`` hold each client's rows flattened and zero-padded to
    whole (8, 128) tiles: shape ``(N, R // 128, 128)``. The TPU's default
    layout for that shape keeps the client axis major, each client's block
    whole tiles of its own, so a cohort is K block copies
    (``gather_clients``). Unpacked, ``(N, max_n, 784)`` gets a default
    layout with the client axis minor (``{0,2,1}`` at MNIST's shape), on
    which every client's copy touches the whole stack. A pinned
    non-default layout does not hold under JAX 0.9's persistent
    compilation cache: an array made by a cached program reports the
    default layout, and the gather is then compiled for that one.
    ``x_rows`` / ``y_rows`` are one client's unpacked shapes; ``n`` the
    (N,) client sizes.
    """
    x: object
    y: object
    n: object
    x_rows: tuple
    y_rows: tuple


def pack_clients(x, y, n) -> ClientStack:
    """Host ``(N, max_n, ...)`` / ``(N, max_n)`` / ``(N,)`` stacks ->
    a host ``ClientStack``."""
    def pack(a):
        flat = np.asarray(a).reshape(len(a), -1)
        pad = -flat.shape[1] % _TILE
        if pad:
            flat = np.pad(flat, ((0, 0), (0, pad)))
        return flat.reshape(len(a), -1, 128)

    return ClientStack(pack(x), pack(y), np.asarray(n),
                       tuple(np.shape(x)[1:]), tuple(np.shape(y)[1:]))


def gather_clients(stack: ClientStack, sel, *, sharded: bool = False):
    """``(x[sel], y[sel], n[sel])`` of the pinned client stack, unpacked
    to ``(K, max_n, ...)``, ``(K, max_n)``, ``(K,)``.

    A ``fori_loop`` over ``sel`` copies each selected client's packed
    block into a ``(K, ...)`` buffer, so the program does not grow with
    K; the cohort is then unpacked. XLA's gather for ``x[sel]`` passes
    over the whole stack instead: on a TPU a ``mini-gather-slice`` of
    every client.

    ``sharded``: the stack's client axis is spread over several devices
    (``fed.parallel.shards_client_axis``). Such a stack keeps the index
    gather: XLA partitions it into a masked gather on each shard and one
    all-reduce of the cohort, where a dynamic slice along the sharded axis
    would all-gather the whole stack first.
    """
    x, y = stack.x, stack.y
    k = sel.shape[0]
    if sharded:
        xs, ys = x[sel], y[sel]
    else:
        def copy_client(i, out):
            return tuple(jax.lax.dynamic_update_index_in_dim(
                o, jax.lax.dynamic_index_in_dim(a, sel[i], 0,
                                                keepdims=False), i, 0)
                for a, o in zip((x, y), out))

        xs, ys = jax.lax.fori_loop(
            0, k, copy_client, tuple(jnp.zeros((k,) + a.shape[1:], a.dtype)
                                     for a in (x, y)))

    def unpack(a, rows):
        return a.reshape(k, -1)[:, :math.prod(rows)].reshape((k,) + rows)

    return unpack(xs, stack.x_rows), unpack(ys, stack.y_rows), stack.n[sel]


def stack_trees(trees):
    """List of pytrees -> one pytree with a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _group_norms(stacked, m):
    """Per-group global parameter norm of an m-stacked pytree -> (m,)."""
    sq = sum(jnp.sum(jnp.square(l.reshape(m, -1)), axis=1)
             for l in jax.tree_util.tree_leaves(stacked))
    return jnp.sqrt(sq)


def _make_round_core(model, *, epochs: int, batch_size: int, lr: float,
                     mu: float, n_groups: int, max_samples: int,
                     eta_g: float = 0.0, assign_fn=None,
                     state_update_fn=None, quarantine: bool = False,
                     quarantine_mult: float = 10.0):
    """The fused round as a pure function with an explicit per-client
    ``alive`` weight — shared by ``make_round_executor`` (alive = ones) and
    ``make_block_executor`` (alive = the staged zero-weight padding mask,
    so ``dropout_rate`` cohorts keep static scan shapes). A client with
    ``alive == 0`` still runs the vmapped solver (dead lanes are cheaper
    than dynamic shapes) but contributes nothing to the aggregation, the
    mean loss, or the discrepancy.

    ``quarantine`` adds an in-program update screen on top of the same
    mask: a client whose local delta is non-finite (NaN/Inf anywhere) or
    whose delta norm exceeds ``quarantine_mult`` × the cohort median is
    folded into the zero-weight path — its delta is zeroed, its final
    local model is replaced by its group's round-start parameters (so
    FeSEM's state scatter writes something finite), and its alive weight
    drops to 0 before any reduction. Zero weight alone is NOT enough:
    ``0 * NaN = NaN`` would still poison the segment-sum matmul, the mean
    loss, and the discrepancy, which is why the screen rewrites the
    payloads rather than just down-weighting them."""
    m = n_groups
    solve = client_lib.make_local_solver(
        model, epochs=epochs, batch_size=batch_size, lr=lr, mu=mu,
        max_samples=max_samples)
    loss_one = client_lib.client_mean_loss(model)

    def core(group_params, membership, X, Y, n, keys, alive) -> RoundOutput:
        # each stage runs under a named scope, so a profile attributes the
        # compiled round's device time to it (op_name metadata; the
        # program keeps its name)
        state = None
        if assign_fn is not None:
            state = membership
            with jax.named_scope("assign"):
                membership = assign_fn(group_params, X, Y, n, state)
        membership = membership.astype(jnp.int32)
        with jax.named_scope("solver"):
            # each client trains from ITS group's parameters (one gather,
            # no loop)
            my_params = jax.tree_util.tree_map(
                lambda g: g[membership], group_params)
            deltas, finals = jax.vmap(solve)(my_params, X, Y, n, keys)

        K = membership.shape[0]
        ok = None
        n_quarantined = jnp.int32(0)
        if quarantine:
            with jax.named_scope("quarantine"):
                d_sq = sum(jnp.sum(jnp.square(d.reshape(K, -1)), axis=1)
                           for d in jax.tree_util.tree_leaves(deltas))
                finite = jnp.isfinite(d_sq)
                norms = jnp.sqrt(jnp.where(finite, d_sq, 0.0))
                # median over the alive, finite updates; NaN comparisons
                # are False, so an all-poisoned cohort quarantines on
                # finiteness alone rather than on the (undefined) outlier
                # threshold
                med = jnp.nanmedian(jnp.where((alive > 0) & finite, norms,
                                              jnp.nan))
                outlier = norms > quarantine_mult * jnp.maximum(med, 1e-12)
                ok = finite & ~outlier
                n_quarantined = jnp.sum((alive > 0) & ~ok).astype(jnp.int32)
                okb = lambda t: ok.reshape((-1,) + (1,) * (t.ndim - 1))
                deltas = jax.tree_util.tree_map(
                    lambda d: jnp.where(okb(d), d, 0.0), deltas)
                finals = jax.tree_util.tree_map(
                    lambda f, p: jnp.where(okb(f), f, p), finals, my_params)
                alive = alive * ok.astype(alive.dtype)

        with jax.named_scope("aggregate"):
            # intra-group FedAvg (Alg. 2): segment-sum with n_i weights
            # normalized within each group
            onehot = jax.nn.one_hot(membership, m, dtype=jnp.float32)  # (K, m)
            w = n.astype(jnp.float32) * alive
            group_tot = onehot.T @ w                                   # (m,)
            norm_w = w[:, None] * onehot / jnp.maximum(group_tot[None],
                                                       1e-9)

            def agg(d):
                flat = d.reshape(d.shape[0], -1)                       # (K, p)
                return (norm_w.T @ flat).reshape((m,) + d.shape[1:])

            agg_delta = jax.tree_util.tree_map(agg, deltas)
            occupied = (group_tot > 0).astype(jnp.float32)
            tilde = jax.tree_util.tree_map(
                lambda gp, gd: gp + occupied.reshape(
                    (-1,) + (1,) * (gp.ndim - 1)) * gd,
                group_params, agg_delta)

        with jax.named_scope("mean_loss"):
            # mean local training loss of the final local models (what
            # History reports as mean_loss — one extra forward pass,
            # n_i-weighted)
            per_client_loss = jax.vmap(loss_one)(finals, X, Y, n)
            if ok is not None:
                # a quarantined client's batch may itself be poisoned, so
                # even the sanitized finals can evaluate to NaN on it
                per_client_loss = jnp.where(ok, per_client_loss, 0.0)
            mean_loss = jnp.sum(per_client_loss * w) / jnp.maximum(
                jnp.sum(w), 1e-9)

        with jax.named_scope("discrepancy"):
            # eq. 4 discrepancy: each client vs its group's intra-aggregated
            # model
            tilde_mine = jax.tree_util.tree_map(lambda t: t[membership],
                                                tilde)
            disc_sq = sum(
                jnp.sum(jnp.square((f - t).reshape(K, -1)), axis=1)
                for f, t in zip(jax.tree_util.tree_leaves(finals),
                                jax.tree_util.tree_leaves(tilde_mine)))
            discrepancy = jnp.sum(jnp.sqrt(disc_sq) * alive) / \
                jnp.maximum(jnp.sum(alive), 1e-9)

        with jax.named_scope("aggregate"):
            # inter-group aggregation (Alg. 2 lines 17-19), stacked form
            if eta_g > 0.0 and m > 1:
                norms = jnp.maximum(_group_norms(tilde, m), 1e-12)

                def inter(t):
                    nm = t / norms.reshape((-1,) + (1,) * (t.ndim - 1))
                    return t + eta_g * (jnp.sum(nm, 0, keepdims=True) - nm)

                new_groups = jax.tree_util.tree_map(inter, tilde)
            else:
                new_groups = tilde

            global_params = jax.tree_util.tree_map(
                lambda g: jnp.mean(g, axis=0), new_groups)
            group_delta_flat = jax.vmap(flatten_updates)(
                jax.tree_util.tree_map(lambda a, b: a - b,
                                       new_groups, group_params))
        if assign_fn is not None and state_update_fn is not None:
            with jax.named_scope("assign"):
                state = state_update_fn(state, membership, deltas, finals)
        return RoundOutput(new_groups, global_params, agg_delta,
                           group_delta_flat, discrepancy, membership, state,
                           mean_loss, n_quarantined)

    return core


def make_round_executor(model, *, epochs: int, batch_size: int, lr: float,
                        mu: float, n_groups: int, max_samples: int,
                        eta_g: float = 0.0, assign_fn=None,
                        state_update_fn=None, quarantine: bool = False,
                        quarantine_mult: float = 10.0):
    """Returns round_fn(group_params, membership, X, Y, n, keys) -> RoundOutput.

    group_params: pytree with leading axis m; membership: (K,) int group id
    per selected client; X: (K, max_n, ...); Y: (K, max_n); n: (K,);
    keys: (K, 2) uint32. Pure function of arrays — jit/pjit it at the call
    site (the trainers jit it; the mesh dry-run lowers it under pjit).

    Dynamic assignment (IFCA / FeSEM): pass
      assign_fn(group_params, X, Y, n, state) -> (K,) int membership
    and the second positional argument of round_fn becomes the opaque
    assignment *state* pytree instead of a membership vector — the cluster
    estimate is computed inside the compiled round and fed straight into the
    gather/segment-sum. An optional
      state_update_fn(state, membership, deltas, finals) -> new state
    keeps per-client state (e.g. FeSEM's flattened local models) on device
    across rounds via an in-program scatter; the updated state is returned
    in ``RoundOutput.assign_state``.

    ``quarantine=True`` screens non-finite / norm-outlier client updates
    into the zero-weight path (see ``_make_round_core``) and reports the
    count in ``RoundOutput.n_quarantined``.
    """
    core = _make_round_core(
        model, epochs=epochs, batch_size=batch_size, lr=lr, mu=mu,
        n_groups=n_groups, max_samples=max_samples, eta_g=eta_g,
        assign_fn=assign_fn, state_update_fn=state_update_fn,
        quarantine=quarantine, quarantine_mult=quarantine_mult)

    def round_fn(group_params, membership, X, Y, n, keys) -> RoundOutput:
        return core(group_params, membership, X, Y, n, keys,
                    jnp.ones(n.shape[0], jnp.float32))

    return round_fn


def make_block_executor(model, *, epochs: int, batch_size: int, lr: float,
                        mu: float, n_groups: int, max_samples: int,
                        eta_g: float = 0.0, assign_fn=None,
                        state_update_fn=None, make_state=None,
                        state_to_aux=None, quarantine: bool = False,
                        quarantine_mult: float = 10.0,
                        sharded_stack: bool = False):
    """Returns block_fn(carry, train_stack, test_stack, idx, keys, alive,
    do_eval) -> (carry, (mean_loss, discrepancy, correct, total,
    n_quarantined)) — B fused rounds as ONE ``jax.lax.scan`` dispatch over
    the pinned stacks.

    carry (the donated round-to-round state):
      ``group_params``  m-stacked pytree, updated in place round to round
      ``global_params`` auxiliary global model (mean of groups)
      ``group_delta``   (m, d_w) latest flattened update directions (eq. 9)
      ``membership``    (N+1,) int32 — every client's group id (-1 = cold),
                        row N is the scatter trash row for padded clients
      ``aux``           framework state (FeSEM: (N+1, d_w) local_flat with
                        the same trash row) or None

    train_stack: the pinned ``ClientStack``; test_stack: the pinned
    ``(x, y, n)`` test stacks — client batches are gathered *in-program*
    (``gather_clients``; ``sharded_stack`` where the mesh spreads its
    client axis), so no per-round H2D. idx: (B, K) int32 staged cohorts;
    keys: (B, K, 2) uint32; alive: (B, K) float32 zero-weight padding mask
    (``dropout_rate`` survivors first, padding after — padded lanes
    aggregate with weight 0 and scatter to the trash row); do_eval: (B,) bool eval-cadence mask
    (``FedConfig.eval_every``). Per-round metrics come back stacked (B,):
    mean_loss, discrepancy, the fused grouped-eval correct/total counts
    (0 where do_eval is False) — ints, so the host-side accuracy division
    reproduces the per-round path bit for bit — and the per-round
    quarantine counts (all 0 when ``quarantine`` is off).

    make_state(aux, idx, membership) builds the per-round assignment state
    from the carried ``aux`` and the carried (N+1,) membership table
    (FeSEM: {"local_flat": aux, "idx": idx}; LCFL gathers the cohort's
    current groups from the membership carry for its hysteresis rule);
    state_to_aux extracts the updated aux from ``RoundOutput.assign_state``.
    With ``assign_fn`` but no ``make_state`` the state is None (IFCA);
    without ``assign_fn`` membership is gathered from the carry (static
    frameworks).

    jit with ``donate_argnums=(0,)`` (``fed.parallel
    .make_sharded_block_executor`` does) so the carry buffers are reused
    instead of reallocated every block.
    """
    core = _make_round_core(
        model, epochs=epochs, batch_size=batch_size, lr=lr, mu=mu,
        n_groups=n_groups, max_samples=max_samples, eta_g=eta_g,
        assign_fn=assign_fn, state_update_fn=state_update_fn,
        quarantine=quarantine, quarantine_mult=quarantine_mult)
    eval_correct = client_lib.grouped_eval_correct(model)

    def block_fn(carry, train_stack, test_stack, idx, keys, alive, do_eval):
        Xt, Yt, nt = test_stack

        def step(c, xs):
            ix, ks, al, ev = xs
            with jax.named_scope("stage"):
                x, y, n = gather_clients(train_stack, ix,
                                         sharded=sharded_stack)
            trash = c["membership"].shape[0] - 1       # row N: padded lanes
            ix_eff = jnp.where(al > 0, ix, trash).astype(jnp.int32)
            if assign_fn is None:
                arg = c["membership"][ix]
            elif make_state is not None:
                arg = make_state(c["aux"], ix_eff, c["membership"])
            else:
                arg = None
            out = core(c["group_params"], arg, x, y, n, ks, al)
            membership = c["membership"].at[ix_eff].set(out.membership)
            aux = c["aux"]
            if state_to_aux is not None:
                aux = state_to_aux(out.assign_state)
            new_c = dict(group_params=out.group_params,
                         global_params=out.global_params,
                         group_delta=out.group_delta_flat,
                         membership=membership, aux=aux)
            correct, total = jax.lax.cond(
                ev,
                lambda gp, mem: eval_correct(gp, mem[:-1], Xt, Yt, nt),
                lambda gp, mem: (jnp.int32(0), jnp.int32(0)),
                out.group_params, membership)
            return new_c, (out.mean_loss, out.discrepancy, correct, total,
                           out.n_quarantined)

        return jax.lax.scan(step, carry, (idx, keys, alive, do_eval))

    return block_fn


def staleness_weight(staleness, *, alpha: float = 1.0, beta: float = 0.0):
    """FedAsync mixing weight w = alpha * (staleness + 1)^(-beta).

    ``staleness`` counts, per group, how many folds landed between this
    dispatch's parameter snapshot and its own fold (0 = fresh). Properties
    the async runtime relies on (tested in tests/test_async.py):

      * s = 0 reduces to exactly ``alpha`` (1^(-beta) == 1.0 in IEEE),
      * monotone non-increasing in s for beta >= 0,
      * alpha = 1, beta = 0 gives exactly 1.0 for every staleness — the
        equivalence mode whose fold is a bitwise passthrough of the
        dispatch result (``make_staleness_fold`` special-cases w == 1).

    Host-side numpy (the weights are (m,) scalars computed at fold time).
    """
    s = np.asarray(staleness, np.float64)
    if np.any(s < 0):
        raise ValueError(f"negative staleness {s}")
    return np.asarray(alpha * (s + 1.0) ** (-float(beta)), np.float32)


def _mix_weighted(weights):
    """Per-leaf convex mix new = (1-w)*cur + w*res over the leading group
    axis, with w == 1.0 an exact bitwise passthrough of ``res`` (0*cur +
    1*res is NOT bit-exact when cur is -0.0 or non-finite, so the
    passthrough is a ``where`` select, not arithmetic)."""
    def mix(cur, res):
        w = weights.reshape((-1,) + (1,) * (res.ndim - 1)).astype(res.dtype)
        return jnp.where(w == 1.0, res, (1.0 - w) * cur + w * res)
    return mix


def make_async_dispatch_executor(model, *, epochs: int, batch_size: int,
                                 lr: float, mu: float, n_groups: int,
                                 max_samples: int, eta_g: float = 0.0,
                                 assign_fn=None, state_update_fn=None,
                                 make_state=None, state_to_aux=None,
                                 quarantine: bool = False,
                                 quarantine_mult: float = 10.0,
                                 sharded_stack: bool = False):
    """Returns dispatch_fn(carry, train_stack, idx, keys, alive) ->
    (result_carry, (mean_loss, discrepancy, n_quarantined, membership)) —
    ONE staged round computed against a *snapshot* carry, for the bounded
    in-flight async window (``FedConfig.async_depth``).

    This is exactly one ``make_block_executor`` scan step (same core, same
    in-program gather from the pinned stacks, same trash-row scatter
    convention), minus the in-program eval — the async loop evaluates at
    *fold* time, on the folded parameters, through the same fused grouped
    eval program. The snapshot carry is NOT donated (at depth D > 1 it is
    shared with the server's live params and other in-flight dispatches);
    the *result* carry is per-dispatch and donated into the staleness fold
    (``make_staleness_fold``). The cohort's post-assignment membership
    rides out with the metrics so the fold can bump the touched groups'
    staleness clocks without an extra device fetch.
    """
    core = _make_round_core(
        model, epochs=epochs, batch_size=batch_size, lr=lr, mu=mu,
        n_groups=n_groups, max_samples=max_samples, eta_g=eta_g,
        assign_fn=assign_fn, state_update_fn=state_update_fn,
        quarantine=quarantine, quarantine_mult=quarantine_mult)

    def dispatch_fn(carry, train_stack, idx, keys, alive):
        with jax.named_scope("stage"):
            x, y, n = gather_clients(train_stack, idx,
                                     sharded=sharded_stack)
        trash = carry["membership"].shape[0] - 1
        ix_eff = jnp.where(alive > 0, idx, trash).astype(jnp.int32)
        if assign_fn is None:
            arg = carry["membership"][idx]
        elif make_state is not None:
            arg = make_state(carry["aux"], ix_eff, carry["membership"])
        else:
            arg = None
        out = core(carry["group_params"], arg, x, y, n, keys, alive)
        membership = carry["membership"].at[ix_eff].set(out.membership)
        aux = carry["aux"]
        if state_to_aux is not None:
            aux = state_to_aux(out.assign_state)
        result = dict(group_params=out.group_params,
                      global_params=out.global_params,
                      group_delta=out.group_delta_flat,
                      membership=membership, aux=aux)
        return result, (out.mean_loss, out.discrepancy, out.n_quarantined,
                        out.membership)

    return dispatch_fn


def make_staleness_fold():
    """Returns fold_fn(current, result, idx, alive, weights) -> carry —
    fold a completed async dispatch into the server's *current* carry with
    per-group staleness weights (``staleness_weight``).

      * group_params: per-group convex mix (1-w)·current + w·result, with
        w == 1.0 a bitwise ``where`` passthrough of the result,
      * global_params: the result's own auxiliary model when every weight
        is 1.0 (bitwise — the D=1 equivalence mode), the mean of the
        folded groups otherwise,
      * group_delta: the dispatch's flattened update directions (eq.-9
        cold-start routing keys off the *direction*, not the magnitude),
      * membership / aux: only the cohort's trash-row-redirected lanes are
        scattered from the result, so at depth D > 1 concurrent dispatches
        merge row-wise (last fold wins on overlapping rows) instead of one
        dispatch's full-table snapshot clobbering the other's writes.

    jit with ``donate_argnums=(0, 1)`` (the engine does): the current
    carry and the per-dispatch result are both consumed, so the folded
    carry reuses their buffers — in-flight dispatches already enqueued
    against the old buffers execute before the fold on the device stream.
    """
    def fold_fn(current, result, idx, alive, weights):
        trash = current["membership"].shape[0] - 1
        ix_eff = jnp.where(alive > 0, idx, trash).astype(jnp.int32)
        membership = current["membership"].at[ix_eff].set(
            result["membership"][ix_eff])
        aux = current["aux"]
        if aux is not None:
            aux = aux.at[ix_eff].set(result["aux"][ix_eff])
        mix = _mix_weighted(weights)
        groups = jax.tree_util.tree_map(mix, current["group_params"],
                                        result["group_params"])
        all_one = jnp.all(weights == 1.0)
        global_params = jax.tree_util.tree_map(
            lambda res_g, g: jnp.where(all_one, res_g, jnp.mean(g, axis=0)),
            result["global_params"], groups)
        return dict(group_params=groups, global_params=global_params,
                    group_delta=result["group_delta"],
                    membership=membership, aux=aux)

    return fold_fn


def make_param_fold():
    """Returns fold_fn(current_groups, result_groups, result_global,
    weights) -> (folded_groups, folded_global) — the carry-less staleness
    fold of the *streamed* async path, where membership / FeSEM rows stay
    host-resident and only the m-stacked group parameters live on device.
    Same mixing semantics as ``make_staleness_fold`` (w == 1.0 is a
    bitwise passthrough, matching the synchronous per-round adoption
    ``group_params = out.group_params; params = out.global_params``)."""
    def fold_fn(current_groups, result_groups, result_global, weights):
        mix = _mix_weighted(weights)
        groups = jax.tree_util.tree_map(mix, current_groups, result_groups)
        all_one = jnp.all(weights == 1.0)
        folded_global = jax.tree_util.tree_map(
            lambda res_g, g: jnp.where(all_one, res_g, jnp.mean(g, axis=0)),
            result_global, groups)
        return groups, folded_global

    return fold_fn


def serial_reference_round(batch_solver, group_params_list, membership,
                           X, Y, n, keys, *, eta_g: float = 0.0):
    """The seed per-group round loop — m solver dispatches plus host-side
    aggregation. Kept as the numerical oracle for the single-dispatch
    executor (tests) and as the baseline side of BENCH_round_exec.json.

    batch_solver: ``client.make_batch_solver`` product; group_params_list:
    list of m pytrees; membership: (K,) numpy int array; the rest as in
    ``make_round_executor`` (keys are per-client, shared with the fused path
    so both draw identical mini-batches).
    """
    m = len(group_params_list)
    tilde, disc, _ = _serial_group_update(
        batch_solver, group_params_list, membership, X, Y, n, keys)
    new_list = server_lib.inter_group_aggregate(tilde, eta_g)
    group_delta = jnp.stack([
        flatten_updates(server_lib.tree_sub(new_list[j], group_params_list[j]))
        for j in range(m)])
    global_params = server_lib.tree_mean(new_list)
    return (new_list, global_params, group_delta, disc)


def _serial_group_update(batch_solver, group_params_list, membership,
                         X, Y, n, keys, collect_finals: bool = False):
    """Shared tail of the retired per-group rounds: one solver launch per
    non-empty cluster, weighted intra-group aggregation, host discrepancy.
    collect_finals additionally flattens each member's final local model
    (FeSEM's host-side local_flat rebuild)."""
    m = len(group_params_list)
    new_list = list(group_params_list)
    disc_sum, disc_n = 0.0, 0
    finals_by_client = {}
    for j in range(m):
        members = np.where(np.asarray(membership) == j)[0]
        if len(members) == 0:
            continue
        sel = jnp.asarray(members)
        deltas, finals = batch_solver(group_params_list[j], X[sel], Y[sel],
                                      n[sel], keys[sel])
        agg = server_lib.weighted_delta(deltas, n[sel])
        new_list[j] = server_lib.apply_delta(group_params_list[j], agg)
        diffs = jax.vmap(lambda f: server_lib.tree_norm(
            server_lib.tree_sub(f, new_list[j])))(finals)
        disc_sum += float(jnp.sum(diffs))
        disc_n += len(members)
        if collect_finals:
            flats = np.asarray(jax.vmap(flatten_updates)(finals))
            for mi, fi in zip(members, flats):
                finals_by_client[int(mi)] = fi
    return new_list, disc_sum / max(disc_n, 1), finals_by_client


def serial_ifca_round(batch_solver, loss_fn, group_params_list,
                      X, Y, n, keys):
    """The retired IFCA round: host-side argmin-loss cluster estimation
    (one loss dispatch per group) followed by one solver launch per
    non-empty cluster — kept as the equivalence oracle for the fused
    assignment stage and the baseline side of BENCH_round_exec.json.

    loss_fn: ``client.make_loss_eval_fn`` product. Returns
    (new group list, membership (K,) numpy, discrepancy).
    """
    losses = jnp.stack([loss_fn(p, X, Y, n) for p in group_params_list])
    membership = np.asarray(jnp.argmin(losses, axis=0))
    new_list, disc, _ = _serial_group_update(
        batch_solver, group_params_list, membership, X, Y, n, keys)
    return new_list, membership, disc


def serial_fesem_round(batch_solver, group_params_list, local_flat,
                       X, Y, n, keys):
    """The retired FeSEM round: host numpy ℓ2 E-step over flattened centers,
    per-group M-step (center = weighted average of members' final local
    models), and a host rebuild of the per-client flattened-model matrix.

    local_flat: (K, d_w) flattened local models of the *selected* clients.
    Returns (new group list, membership, new local_flat, discrepancy).
    """
    centers = np.stack([np.asarray(flatten_updates(p))
                        for p in group_params_list])
    lf = np.asarray(local_flat)
    d2 = ((lf[:, None, :] - centers[None]) ** 2).sum(-1)
    membership = d2.argmin(1)
    # M-step ≡ intra-group FedAvg: avg_w(finals) = center + avg_w(deltas)
    new_list, disc, finals_by_client = _serial_group_update(
        batch_solver, group_params_list, membership, X, Y, n, keys,
        collect_finals=True)
    new_local = lf.copy()
    for mi, fi in finals_by_client.items():
        new_local[mi] = fi
    return new_list, membership, new_local, disc
