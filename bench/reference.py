"""Plain reference for FedGroup rounds (Algorithm 2 and eq. 9), in
``jax.numpy`` at full float32 matmul precision, one client at a time.

It imports nothing of the program. What it takes in is data (the client
arrays the benchmark generated), the cohort schedule, the state a round
starts from and the PRNG key the round's draws derive from. The draws
follow FedGroup's definition of the local solver: client ``i`` of a
cohort gets key ``split(sk, K)[i]`` where ``key, sk = split(key)`` once
per round (and once more before it when the round has newcomers); each
SGD step splits that key and draws a batch of ``B`` rows uniformly, with
replacement, from the client's ``n_i`` samples; a client takes
``E * ceil(n_i / B)`` steps.

The forward pass ``apply(p, x)`` is the configuration's kind module's
(``bench/models/<kind>.py``); everything here is generic over its
parameter pytree, walked in ``jax.tree_util`` order (the order the
program flattens an update in), and over labels of any trailing shape:
one a row, or one a token. A group state is the same pytree with a
leading group axis. ``dtype`` sets the precision the reference computes
in: float32 is the reference, bfloat16 is the lower-precision control.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

def batch_loss(apply, p, x, y):
    logits = apply(p, x)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


@partial(jax.jit, static_argnames=("apply", "batch_size", "epochs", "lr"))
def local_sgd(p0, x, y, n, key, *, apply, batch_size: int, epochs: int,
              lr: float):
    """E epochs of minibatch SGD on one client -> final parameters."""
    n = jnp.maximum(n, 1)
    steps = epochs * ((n + batch_size - 1) // batch_size)
    grad = jax.grad(partial(batch_loss, apply))

    def body(_, carry):
        p, key = carry
        key, sk = jax.random.split(key)
        rows = jax.random.randint(sk, (batch_size,), 0, n)
        g = grad(p, x[rows], y[rows])
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), key

    with jax.default_matmul_precision("highest"):
        p, _ = jax.lax.fori_loop(0, steps, body, (p0, key))
    return p


@partial(jax.jit, static_argnames=("apply",))
def client_loss(p, x, y, n, *, apply):
    """Mean cross-entropy of ``p`` over a client's ``n`` valid rows (a
    row's own loss is the mean over its labels)."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(apply(p, x).astype(jnp.float32), -1)
    ce = -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]
    if y.ndim > 1:
        ce = jnp.mean(ce.reshape(y.shape[0], -1), -1)
    return jnp.sum(jnp.where(jnp.arange(y.shape[0]) < n, ce, 0.0)) / n


@partial(jax.jit, static_argnames=("apply",))
def client_correct(p, x, y, n, *, apply):
    """Correct predictions of ``p`` over a client's ``n`` valid rows."""
    with jax.default_matmul_precision("highest"):
        pred = jnp.argmax(apply(p, x), -1)
    valid = jnp.arange(y.shape[0]) < n
    return jnp.sum((pred == y)
                   & valid.reshape(valid.shape + (1,) * (y.ndim - 1)))


def flat(p) -> np.ndarray:
    """A parameter pytree as one float32 vector, leaves in
    ``jax.tree_util`` order."""
    return np.concatenate([np.asarray(v, np.float32).ravel()
                           for v in jax.tree_util.tree_leaves(p)])


def group(gp, j: int):
    return jax.tree_util.tree_map(lambda v: v[j], gp)


def _cast(tree, dtype):
    return jax.tree_util.tree_map(lambda v: jnp.asarray(v, dtype), tree)


class Reference:
    """FedGroup rounds over pinned client data, one client at a time.

    ``data`` is the benchmark's data dict (``bench.generators``): padded
    arrays, or for a streamed population a ``client_fn``; ``fed`` the
    configuration's ``fed`` block, ``apply`` its kind's forward pass.
    ``drop_half`` and ``alter_client``
    plant faults (the reference put in the program's place, for
    calibration): the second half of every cohort left out, and the train
    labels of the cohort's client ``alter_client`` shifted by one class,
    so that its update answers the wrong question; ``misassign`` sends a
    round's first newcomer to its most dissimilar group."""

    def __init__(self, data: dict, fed: dict, apply, dtype=jnp.float32,
                 drop_half: bool = False, alter_client: int | None = None,
                 misassign: bool = False):
        self.d, self.fed, self.apply, self.dtype = data, fed, apply, dtype
        self.drop_half, self.alter_client = drop_half, alter_client
        self.misassign = misassign

    def _client(self, i: int, split: str = "train", alter: bool = False):
        d = self.d
        if "client_fn" in d:
            c = d["client_fn"](i)
            xk, yk = ("x", "y") if split == "train" else ("x_test", "y_test")
            rows = d[f"max_{split}"]
            x = np.zeros((rows,) + c[xk].shape[1:], np.float32)
            y = np.zeros((rows,) + c[yk].shape[1:], np.int32)
            x[:len(c[xk])] = c[xk]
            y[:len(c[yk])] = c[yk]
            x = jnp.asarray(x, self.dtype)
        else:
            x = jnp.asarray(d[f"x_{split}"][i], self.dtype)
            y = d[f"y_{split}"][i]
        if alter:
            y = (y + 1) % int(d["n_classes"])
        return x, jnp.asarray(y), int(d[f"n_{split}"][i])

    def _solve(self, p, i, key, epochs, alter: bool = False):
        x, y, n = self._client(i, alter=alter)
        return local_sgd(_cast(p, self.dtype), x, y, n, key, apply=self.apply,
                         batch_size=int(self.fed["batch_size"]),
                         epochs=int(epochs), lr=float(self.fed["lr"]))

    def cold_assign(self, glob, group_dir, ids, key, follow=None):
        """Eq. 9: each newcomer's one-epoch update from the auxiliary
        global model joins the group whose latest update direction is the
        least cosine-dissimilar. With ``follow`` (the groups the program
        chose) the newcomers join those instead, and each one's gap is how
        far the chosen group's dissimilarity lies above the least.
        -> (groups, gaps, key after the draw)."""
        key, sk = jax.random.split(key)
        keys = jax.random.split(sk, len(ids))
        groups, gaps = [], []
        gd = np.asarray(group_dir, np.float64)
        gd = gd / np.maximum(np.linalg.norm(gd, axis=1, keepdims=True),
                             1e-12)
        for c, (i, k) in enumerate(zip(ids, keys)):
            final = self._solve(glob, int(i), k, 1)
            d = flat(final).astype(np.float64) - flat(glob)
            dis = (1.0 - gd @ (d / max(np.linalg.norm(d), 1e-12))) / 2.0
            g = int(np.argmin(dis))
            if self.misassign and c == 0:
                g = int(np.argmax(dis))
            if follow is not None:
                g = int(follow[c])
            groups.append(g)
            gaps.append(float(dis[g] - dis.min()))
        return np.asarray(groups, np.int64), gaps, key

    def round(self, state: dict, idx, eval_ids=None, follow=None) -> dict:
        """One round from ``state`` = {"groups" (m-stacked pytree),
        "glob", "group_dir" (m, d_w), "membership" (N,), "key"} -> the
        next state plus this round's "loss", its newcomers' "assign_gaps"
        and, where ``eval_ids`` is given, "correct": the test predictions
        over those of them that are assigned. ``follow`` is the program's
        membership after the round: newcomers join the groups it holds."""
        m = int(self.fed["n_groups"])
        idx = np.asarray(idx)
        mem = np.array(state["membership"], np.int64)
        key = state["key"]
        cold = idx[mem[idx] < 0]
        gaps = []
        if len(cold):
            mem[cold], gaps, key = self.cold_assign(
                state["glob"], state["group_dir"], cold, key,
                None if follow is None else np.asarray(follow)[cold])
        key, sk = jax.random.split(key)
        keys = jax.random.split(sk, len(idx))
        if self.drop_half:
            idx, keys = idx[:len(idx) // 2], keys[:len(idx) // 2]
        tree = jax.tree_util
        gp = tree.tree_map(lambda v: np.asarray(v, np.float64),
                           state["groups"])
        num = tree.tree_map(np.zeros_like, gp)
        wsum = np.zeros(m)
        loss_num = 0.0
        for c, (i, k) in enumerate(zip(idx, keys)):
            g = int(mem[i])
            start = group(state["groups"], g)
            alter = c == self.alter_client
            final = self._solve(start, int(i), k, self.fed["local_epochs"],
                                alter)
            x, y, n = self._client(int(i), alter=alter)
            loss_num += n * float(client_loss(final, x, y, n,
                                              apply=self.apply))
            for acc, f, s0 in zip(tree.tree_leaves(num),
                                  tree.tree_leaves(final),
                                  tree.tree_leaves(start)):
                acc[g] += n * (np.asarray(f, np.float64)
                               - np.asarray(s0, np.float64))
            wsum[g] += n
        scale = 1.0 / np.where(wsum > 0, wsum, 1.0)
        new = tree.tree_map(
            lambda v, u: v + u * scale.reshape((m,) + (1,) * (v.ndim - 1)),
            gp, num)
        groups = tree.tree_map(lambda v: v.astype(np.float32), new)
        glob = tree.tree_map(lambda v: v.mean(0).astype(np.float32), new)
        group_dir = np.stack([flat(group(new, j)) - flat(group(gp, j))
                              for j in range(m)])
        return {"groups": groups, "glob": glob, "group_dir": group_dir,
                "membership": mem, "key": key,
                "loss": loss_num / max(wsum.sum(), 1.0), "assign_gaps": gaps,
                "correct": (None if eval_ids is None
                            else self.correct(groups, mem, eval_ids))}

    def correct(self, groups, mem, ids) -> int:
        """Correct test predictions of the assigned clients among ``ids``
        under their groups' models (the paper's weighted accuracy, as a
        count)."""
        models = [_cast(group(groups, j), self.dtype)
                  for j in range(int(self.fed["n_groups"]))]
        total = 0
        for i in np.asarray(ids)[mem[np.asarray(ids)] >= 0]:
            x, y, n = self._client(int(i), "test")
            total += int(client_correct(models[int(mem[i])], x, y, n,
                                        apply=self.apply))
        return total
