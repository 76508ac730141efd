"""Live-step operation counts against a hand count."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import flops  # noqa: E402


def test_live_steps_count_only_each_clients_own_steps():
    # n = 1, 10, 11, 0 samples, B = 10, E = 20: 20, 20, 40 steps; an
    # empty client is solved as one sample (the solver's floor): 20
    per = 7
    got = flops.live_sgd_flops([1, 10, 11, 0], epochs=20, batch_size=10,
                               per_sample=per)
    assert got == (20 + 20 + 40 + 20) * 10 * per
