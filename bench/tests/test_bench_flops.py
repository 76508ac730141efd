"""Live-step operation counts against a hand count."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import flops  # noqa: E402


def test_mlp_per_sample_by_hand():
    # MLP-512 on 784 features, 26 classes: forward 2*(784*512 + 512*26);
    # backward dW1 2*784*512, dW2 2*512*26, dh 2*512*26
    fwd = 2 * 784 * 512 + 2 * 512 * 26
    bwd = 2 * 784 * 512 + 2 * 512 * 26 + 2 * 512 * 26
    assert flops.mlp_train_flops_per_sample(784, 512, 26) == fwd + bwd
    assert flops.mlp_train_flops_per_sample(784, 512, 26) == 1_685_504


def test_live_steps_count_only_each_clients_own_steps():
    # n = 1, 10, 11, 0 samples, B = 10, E = 20: 20, 20, 40 steps; an
    # empty client is solved as one sample (the solver's floor): 20
    per = 7
    got = flops.live_sgd_flops([1, 10, 11, 0], epochs=20, batch_size=10,
                               per_sample=per)
    assert got == (20 + 20 + 40 + 20) * 10 * per
