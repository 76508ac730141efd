"""Mesh-parallel FedGroup — the paper's technique as a first-class
distributed workload (the TPU-native replacement for the per-client loop).

The serial trainers' sharding helpers live here too: ``default_data_mesh``
(a 1-D "data" mesh over all visible devices, None on one device),
``default_fed_mesh`` (its 2-D ``(data, model)`` generalization, picked by
``REPRO_MODEL_AXIS``), and ``make_sharded_executor`` (jit of a round
executor with the client axis of every K-leading input sharded over the
mesh's data axes and — on a 2-D mesh — the m-stacked group parameters
sharded over "model" along the local solver's largest divisible parameter
dim, per ``sharding.specs.group_param_pspec``; a model axis of size 1
replicates, so the 1-device and 1-D paths are special cases) — the same
fused round runs client-parallel everywhere, not just under the dry-run
below. ``put_sharded_cohort`` is the multi-host-style feeding primitive:
per-data-shard host arrays go device-side with one H2D put per shard and
are assembled into a single global array via
``jax.make_array_from_single_device_arrays`` (see docs/scaling.md).

Two jittable entry points, both lowered by the FedGroup dry-run:

  parallel_round      one FedGroup communication round: K clients sharded
                      over the mesh "data" axis, each doing E epochs of local
                      SGD from its group's parameters, followed by per-group
                      weighted aggregation (segment-sum + psum).

  group_cold_start_distributed
                      Algorithm 3 at production scale: the pre-training
                      update matrix ΔW (n_pre × d_w, d_w up to hundreds of
                      millions) is sharded over the "model" axis along d_w;
                      randomized SVD + EDC embedding run as sharded matmuls.
                      ``qr_impl='cholesky'`` replaces tall-skinny QR with
                      CholeskyQR2 (Gram matrix + psum of an (k×k) block) —
                      the beyond-paper collective optimization (§Perf).

Both are pure functions of arrays, so they lower/compile under pjit with
the shardings chosen in launch/fed_dryrun.py.
"""
from __future__ import annotations

import os
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_fed_mesh, make_mesh
from repro.models.modules import flatten_updates
from repro.sharding.specs import (MP_AXIS, block_staged_pspec, cohort_pspec,
                                  data_axis_names, group_param_pspec)


# ---------------------------------------------------------------------------
# Client-axis sharding for the serial trainers
# ---------------------------------------------------------------------------

def default_data_mesh():
    """A 1-D ("data",) mesh over all visible devices, or None on a single
    device — the trainers' auto-detected executor sharding (the 1-device
    None answer selects the plain-jit path)."""
    n = jax.device_count()
    if n <= 1:
        return None
    return make_mesh((n,), ("data",))


def default_fed_mesh(model_axis: int | None = None):
    """The trainers' auto-detected mesh, generalized to 2-D.

    ``model_axis`` (default: ``REPRO_MODEL_AXIS`` env var, 1) is the size
    of the "model" axis the local solver's parameter dim shards over;
    the remaining devices form the "data" (client) axis. ``model_axis=1``
    degrades exactly to ``default_data_mesh()`` — the 1-D path (and None
    on a single device) is the special case, so existing behaviour is
    unchanged unless a model axis is asked for.
    """
    if model_axis is None:
        model_axis = int(os.environ.get("REPRO_MODEL_AXIS", "1"))
    if model_axis <= 1:
        return default_data_mesh()
    n = jax.device_count()
    if n % model_axis:
        raise ValueError(f"model_axis={model_axis} does not divide the "
                         f"{n} visible devices")
    return make_fed_mesh(n // model_axis, model_axis)


def mesh_data_shards(mesh) -> int:
    """Number of data-axis slices of ``mesh`` (1 for mesh=None) — the
    shard count of the client axis and of ``fed.store.ShardedClientStore``
    cohort slices."""
    if mesh is None:
        return 1
    total = 1
    for a in data_axis_names(mesh):
        total *= mesh.shape[a]
    return total


def _splits(n: int, total: int) -> bool:
    """Whether ``total`` data slices split a leading axis of ``n``."""
    return n > 0 and n % total == 0


def shards_client_axis(mesh, n: int) -> bool:
    """Whether ``shard_client_axis`` spreads a leading (client) axis of
    ``n`` over more than one device of ``mesh``. A program that reads such
    a stack by client (``fed.rounds.gather_clients``) picks its gather by
    this."""
    total = mesh_data_shards(mesh)
    return total > 1 and _splits(n, total)


def shard_client_axis(mesh, tree):
    """device_put every array leaf with its leading (client) axis sharded
    over the mesh *data* axes when divisible, replicated otherwise.
    ``mesh=None`` degrades to a plain asynchronous ``jax.device_put`` — the
    unified H2D entry the population prefetcher uses, so streamed cohorts
    land pre-placed for the executor on one device and on a mesh alike.
    On a 2-D ``(data, model)`` mesh only the data axes consume the client
    axis; the model axis replicates (it shards parameters, not clients).

    Works on arbitrary pytrees, so the dynamic-assignment state (e.g.
    FeSEM's {"local_flat", "idx"}) shards leaf-by-leaf: local_flat by rows
    over all clients, idx over the selected-client axis.
    """
    if mesh is None:
        return jax.tree_util.tree_map(
            lambda l: jax.device_put(jnp.asarray(l)), tree)
    axes = data_axis_names(mesh)
    total = mesh_data_shards(mesh)

    def put(leaf):
        leaf = jnp.asarray(leaf)
        if leaf.ndim >= 1 and _splits(leaf.shape[0], total):
            spec = cohort_pspec(leaf.ndim, data_axes=axes)
        else:
            spec = P(*([None] * leaf.ndim))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, tree)


def put_sharded_cohort(mesh, parts):
    """Assemble per-shard host arrays into one mesh-global cohort array.

    ``parts`` is a list of same-structure pytrees, one per data-axis slice
    (``fed.store.ShardedClientStore.gather_train_shards`` output): shard
    ``s`` holds the rows the mesh's s-th data slice will own. Each shard's
    arrays are device_put *onto that slice's devices only* — one H2D
    transfer per shard, never a host-side concatenation of the full cohort
    — and stitched into a single global array with
    ``jax.make_array_from_single_device_arrays``. On one machine this
    simulates the multi-host feeding path exactly: a real multi-pod
    deployment runs the same code with each host contributing only its
    local shard. Falls back to ``shard_client_axis`` over the concatenated
    cohort when the row count does not divide the data axes (replication —
    the same degradation the non-divisible 1-D path takes).
    """
    n_shards = mesh_data_shards(mesh) if mesh is not None else 1
    if mesh is None or n_shards != len(parts):
        merged = jax.tree_util.tree_map(
            lambda *ls: np.concatenate([np.asarray(l) for l in ls]), *parts)
        return shard_client_axis(mesh, merged)
    axes = data_axis_names(mesh)

    def one(*leaf_parts):
        leaf_parts = [np.asarray(l) for l in leaf_parts]
        rows = sum(l.shape[0] for l in leaf_parts)
        block = rows // n_shards
        if block * n_shards != rows or \
                any(l.shape[0] != block for l in leaf_parts):
            return shard_client_axis(mesh, np.concatenate(leaf_parts))
        gshape = (rows,) + leaf_parts[0].shape[1:]
        sharding = NamedSharding(mesh, cohort_pspec(len(gshape), axes))
        arrs = []
        for dev, index in sharding.addressable_devices_indices_map(
                gshape).items():
            r = index[0]
            lo = 0 if r.start is None else int(r.start)
            arrs.append(jax.device_put(leaf_parts[lo // block], dev))
        return jax.make_array_from_single_device_arrays(
            gshape, sharding, arrs)

    return jax.tree_util.tree_map(one, *parts)


def make_sharded_executor(round_fn, mesh=None):
    """jit ``round_fn`` (a ``fed.rounds.make_round_executor`` product) with
    its client axis sharded over ``mesh``.

    mesh=None (single device) is the plain-jit special case. With a mesh,
    the K-axis inputs (membership or assignment state, X, Y, n, keys) are
    placed with their leading axis sharded over the data axes before
    dispatch, and the m-stacked group parameters are placed per
    ``sharding.specs.group_param_pspec``: replicated on a 1-D (or
    model-axis-1) mesh — the PR-2 behaviour — or sharded over "model"
    along the local solver's largest divisible parameter dim on a 2-D
    ``(data, model)`` mesh. The compiled round then runs client-parallel
    over "data" and solver-parallel over "model", with XLA inserting the
    segment-sum and contraction all-reduces.
    """
    jfn = jax.jit(round_fn)
    if mesh is None:
        return jfn
    model_size = dict(mesh.shape).get(MP_AXIS, 1)
    place_groups = lambda t: jax.tree_util.tree_map(
        lambda l: jax.device_put(
            l, NamedSharding(mesh, group_param_pspec(jnp.shape(l),
                                                     model_size))), t)

    def call(group_params, assign, X, Y, n, keys):
        group_params = place_groups(group_params)
        assign, X, Y, n, keys = (shard_client_axis(mesh, t)
                                 for t in (assign, X, Y, n, keys))
        return jfn(group_params, assign, X, Y, n, keys)

    return call


def make_sharded_block_executor(block_fn, mesh=None):
    """jit ``block_fn`` (a ``fed.rounds.make_block_executor`` product) with
    the round-to-round carry DONATED and, on a mesh, the same placement as
    the per-round executor.

    ``donate_argnums=(0,)`` hands the carry's buffers (m-stacked group
    params, membership, FeSEM local_flat) back to XLA, so B rounds of group
    state update in place instead of reallocating every block — the
    steady-state device allocation win the ``round_block`` bench records.

    mesh=None (single device) is the plain donating-jit special case. With
    a mesh, the carry's m-stacked group params follow
    ``sharding.specs.group_param_pspec`` (replicated at model-axis 1), the
    pinned train/test stacks shard their leading (client) axis over the
    data axes when divisible (``shard_client_axis``), and the staged
    ``(B, K, ...)`` tensors shard their *client* axis — axis 1, the scan
    consumes axis 0 — per ``sharding.specs.block_staged_pspec``. The rest
    of the carry (membership, aux, deltas) replicates: it is O(N + m·d_w),
    gathered/scattered by client id in-program.
    """
    jfn = jax.jit(block_fn, donate_argnums=(0,))
    if mesh is None:
        return jfn
    model_size = dict(mesh.shape).get(MP_AXIS, 1)
    axes = data_axis_names(mesh)
    total = mesh_data_shards(mesh)
    replicate = lambda t: jax.tree_util.tree_map(
        lambda l: jax.device_put(jnp.asarray(l), NamedSharding(
            mesh, P(*([None] * jnp.ndim(l))))), t)
    place_groups = lambda t: jax.tree_util.tree_map(
        lambda l: jax.device_put(l, NamedSharding(
            mesh, group_param_pspec(jnp.shape(l), model_size))), t)

    def place_staged(leaf):
        leaf = jnp.asarray(leaf)
        if leaf.ndim >= 2 and _splits(leaf.shape[1], total):
            spec = block_staged_pspec(leaf.ndim, data_axes=axes)
        else:
            spec = P(*([None] * leaf.ndim))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    def call(carry, train_stack, test_stack, idx, keys, alive, do_eval):
        carry = dict(carry,
                     group_params=place_groups(carry["group_params"]),
                     global_params=place_groups(carry["global_params"]),
                     group_delta=replicate(carry["group_delta"]),
                     membership=replicate(carry["membership"]),
                     aux=replicate(carry["aux"]))
        train_stack = shard_client_axis(mesh, train_stack)
        test_stack = shard_client_axis(mesh, test_stack)
        idx, keys, alive = (jax.tree_util.tree_map(place_staged, t)
                            for t in (idx, keys, alive))
        return jfn(carry, train_stack, test_stack, idx, keys, alive,
                   replicate(do_eval))

    return call


def make_async_dispatch_executor(dispatch_fn, mesh=None):
    """jit ``dispatch_fn`` (a ``fed.rounds.make_async_dispatch_executor``
    product) with the block executor's mesh placement but WITHOUT donating
    the snapshot carry.

    The async runtime (``FedConfig.async_depth``) keeps up to D dispatches
    in flight against the *same* current carry, so the dispatch input must
    stay alive — donation moves to the staleness fold instead
    (``make_async_fold``), which consumes both the current carry and the
    per-dispatch result. mesh=None (single device) is the plain-jit
    special case; with a mesh the carry / pinned stacks / staged cohort
    tensors are placed exactly as ``make_sharded_block_executor`` places
    them (group params per ``group_param_pspec``, the (K,)-leading staged
    arrays over the data axes, the rest replicated).
    """
    jfn = jax.jit(dispatch_fn)
    if mesh is None:
        return jfn
    model_size = dict(mesh.shape).get(MP_AXIS, 1)
    replicate = lambda t: jax.tree_util.tree_map(
        lambda l: jax.device_put(jnp.asarray(l), NamedSharding(
            mesh, P(*([None] * jnp.ndim(l))))), t)
    place_groups = lambda t: jax.tree_util.tree_map(
        lambda l: jax.device_put(l, NamedSharding(
            mesh, group_param_pspec(jnp.shape(l), model_size))), t)

    def call(carry, train_stack, idx, keys, alive):
        carry = dict(carry,
                     group_params=place_groups(carry["group_params"]),
                     global_params=place_groups(carry["global_params"]),
                     group_delta=replicate(carry["group_delta"]),
                     membership=replicate(carry["membership"]),
                     aux=replicate(carry["aux"]))
        train_stack = shard_client_axis(mesh, train_stack)
        idx, keys, alive = (shard_client_axis(mesh, t)
                            for t in (idx, keys, alive))
        return jfn(carry, train_stack, idx, keys, alive)

    return call


def make_async_fold(fold_fn):
    """jit a ``fed.rounds.make_staleness_fold`` product with BOTH the
    current carry and the per-dispatch result donated — the fold is the
    single consumer of each dispatch's output buffers, and on the device
    stream every already-enqueued dispatch that reads the old current
    carry executes before the fold reuses it (dispatch, then fold, are
    enqueued in that order by the async loop). Works on mesh and
    single-device alike: the fold's inputs are outputs of earlier placed
    computations, so GSPMD propagates their shardings.

    The weight-1.0 passthrough keeps BOTH fold inputs live in the select,
    so XLA can alias the output to only one of the two donated trees —
    the resulting "donated buffers were not usable" warning is expected
    and silenced here (the aliasable side still is aliased)."""
    jfn = jax.jit(fold_fn, donate_argnums=(0, 1))

    def call(*args):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jfn(*args)

    return call


# ---------------------------------------------------------------------------
# One round, client-parallel
# ---------------------------------------------------------------------------

def make_parallel_round(model, *, epochs: int, batch_size: int, lr: float,
                        mu: float, n_groups: int, max_samples: int,
                        quarantine: bool = False,
                        quarantine_mult: float = 10.0):
    """Returns round_fn(group_params_stacked, membership, X, Y, n, keys)
      -> (new group params stacked, auxiliary global params, group deltas).

    group_params_stacked: pytree with leading axis m.
    membership: (K,) int group id per selected client.
    X: (K, max_n, ...), Y: (K, max_n), n: (K,), keys: (K, 2) uint32.

    Thin adapter over ``fed.rounds.make_round_executor`` — the same fused
    round the serial trainers dispatch; only the mesh shardings differ
    (chosen in launch/fed_dryrun.py). The executor's extra outputs
    (discrepancy, flattened group deltas) are dead code here and XLA
    eliminates them when this round_fn is jitted. ``quarantine`` installs
    the same in-program update screen as the engine path — the per-client
    norm reductions shard over the data axes with the cohort, and the
    median is a cohort-global reduction the partitioner turns into an
    all-gather, so screening costs no extra dispatch on a mesh either.
    """
    from repro.fed.rounds import make_round_executor
    core = make_round_executor(model, epochs=epochs, batch_size=batch_size,
                               lr=lr, mu=mu, n_groups=n_groups,
                               max_samples=max_samples, eta_g=0.0,
                               quarantine=quarantine,
                               quarantine_mult=quarantine_mult)

    def round_fn(group_params, membership, X, Y, n, keys):
        out = core(group_params, membership, X, Y, n, keys)
        return out.group_params, out.global_params, out.agg_delta

    return round_fn


# ---------------------------------------------------------------------------
# Distributed group cold start (Algorithm 3 at scale)
# ---------------------------------------------------------------------------

def cholesky_qr2(Y):
    """CholeskyQR2: Q from two rounds of Gram-matrix Cholesky.

    For a (d, k) tall-skinny sharded-by-rows Y this needs only two (k, k)
    all-reduces instead of a distributed Householder QR — the beyond-paper
    collective optimization for the cold start.
    """
    def _cqr(A):
        k = A.shape[1]
        G = A.T @ A                                      # (k,k): psum if sharded
        Lc = jnp.linalg.cholesky(G + 1e-8 * jnp.eye(k, dtype=G.dtype))
        # Apply L^-T as a small replicated matmul (NOT solve_triangular on the
        # tall operand — XLA cannot partition that and would all-gather A).
        Linv = jax.scipy.linalg.solve_triangular(
            Lc, jnp.eye(k, dtype=G.dtype), lower=True)   # (k,k) replicated
        Q = A @ Linv.T
        return Q, Lc.T
    Q1, R1 = _cqr(Y)
    Q2, R2 = _cqr(Q1)
    return Q2, R2 @ R1


def rsvd_sharded(dW, m: int, *, n_iter: int = 4, oversample: int = 8,
                 key=None, qr_impl: str = "householder"):
    """Top-m left singular directions of ΔWᵀ, d_w-sharded friendly.

    dW: (n, d_w). All heavy ops are (d_w × small) matmuls; with d_w sharded
    over "model", XLA turns the small Gram products into psums.
    qr_impl: 'householder' (jnp.linalg.qr — baseline) or 'cholesky' (CQR2).
    """
    n, d = dW.shape
    k = min(m + oversample, n)
    if key is None:
        key = jax.random.PRNGKey(0)
    A = dW.astype(jnp.float32).T                         # (d, n)
    qr = jnp.linalg.qr if qr_impl == "householder" \
        else (lambda Y: cholesky_qr2(Y))
    omega = jax.random.normal(key, (n, k), jnp.float32)
    Y = A @ omega
    Q = qr(Y)[0]
    for _ in range(n_iter):
        W = qr(A.T @ Q)[0]
        Q = qr(A @ W)[0]
    B = Q.T @ A                                          # (k, n)
    Ub, s, _ = jnp.linalg.svd(B, full_matrices=False)
    return (Q @ Ub)[:, :m]


def edc_embedding_distributed(dW, m: int, *, key=None,
                              qr_impl: str = "householder",
                              use_kernel: bool = False):
    """ΔW -> (E (n, m) cosine embedding, V). The group-cold-start hot path."""
    V = rsvd_sharded(dW, m, key=key, qr_impl=qr_impl)
    if use_kernel:
        from repro.kernels.ops import cosine_block
        return cosine_block(dW, V), V
    dots = dW.astype(jnp.float32) @ V
    rn = jnp.sqrt(jnp.sum(jnp.square(dW.astype(jnp.float32)), axis=1,
                          keepdims=True))
    cn = jnp.linalg.norm(V, axis=0, keepdims=True)
    return dots / jnp.maximum(rn * cn, 1e-12), V


def kmeans_step(E, centers):
    """One Lloyd iteration on the embedding (jit-friendly)."""
    d2 = jnp.sum(jnp.square(E[:, None, :] - centers[None]), -1)
    assign = jnp.argmin(d2, -1)
    onehot = jax.nn.one_hot(assign, centers.shape[0], dtype=jnp.float32)
    counts = jnp.sum(onehot, 0)
    sums = onehot.T @ E
    new = jnp.where(counts[:, None] > 0,
                    sums / jnp.maximum(counts[:, None], 1), centers)
    return assign, new
