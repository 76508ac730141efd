"""Operation counts from shapes, for the utilization metrics. A model's
operations per sample come from its kind module
(``bench/models/<kind>.py:train_flops_per_sample``); this module counts
the samples.

Only the multiply-adds of the matrix products are counted (2 operations
each); bias adds, activations, the softmax and the optimizer update are
left out, so a share of the peak computed from these counts errs low,
never high.
"""
from __future__ import annotations

import numpy as np


def live_sgd_flops(n_train, *, epochs: int, batch_size: int,
                   per_sample: int) -> int:
    """Operations of the SGD steps that a cohort's local solves require:
    client ``i`` takes ``epochs * ceil(n_i / batch_size)`` steps of
    ``batch_size`` samples. Steps a compiled solver runs past a client's
    own count (masked, changing nothing) are not counted."""
    n = np.maximum(np.asarray(n_train, np.int64), 1)
    steps = epochs * ((n + batch_size - 1) // batch_size)
    return int(steps.sum()) * batch_size * int(per_sample)
