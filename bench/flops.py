"""Operation counts from shapes, for the utilization metrics.

Only the multiply-adds of the matrix products are counted (2 operations
each); bias adds, activations, the softmax and the optimizer update are
left out, so a share of the peak computed from these counts errs low,
never high.
"""
from __future__ import annotations

import numpy as np


def mlp_train_flops_per_sample(in_dim: int, hidden: int,
                               n_classes: int) -> int:
    """Forward and backward operations of one sample through a
    one-hidden-layer MLP: the forward pass's two products, and in the
    backward pass the two weight gradients and the gradient into the
    hidden layer (none flows into the input)."""
    first, second = in_dim * hidden, hidden * n_classes
    forward = 2 * (first + second)
    backward = 2 * first + 2 * 2 * second
    return forward + backward


def live_sgd_flops(n_train, *, epochs: int, batch_size: int,
                   per_sample: int) -> int:
    """Operations of the SGD steps that a cohort's local solves require:
    client ``i`` takes ``epochs * ceil(n_i / batch_size)`` steps of
    ``batch_size`` samples. Steps a compiled solver runs past a client's
    own count (masked, changing nothing) are not counted."""
    n = np.maximum(np.asarray(n_train, np.int64), 1)
    steps = epochs * ((n + batch_size - 1) // batch_size)
    return int(steps.sum()) * batch_size * int(per_sample)
