"""The paper's one-hidden-layer ReLU perceptron (Table 2: MLP-128 on
MNIST, MLP-512 on FEMNIST). Model block: ``{"kind": "mlp", "in_dim",
"hidden", "n_classes", "d_w"}``; a row is ``in_dim`` features with one
class label. Parameters ``{"w1", "b1", "w2", "b2"}``."""
from __future__ import annotations

import jax


def program(model: dict, data: dict):
    from repro.models.paper_models import mlp
    return mlp(model["in_dim"], model["hidden"], model["n_classes"])


def row_shape(model: dict, data: dict) -> tuple:
    return (model["in_dim"],)


def apply(p, x):
    h = jax.nn.relu(x @ p["w1"] + p["b1"])
    return h @ p["w2"] + p["b2"]


def train_flops_per_sample(model: dict, data: dict) -> int:
    """Forward and backward operations of one sample through a
    one-hidden-layer MLP: the forward pass's two products, and in the
    backward pass the two weight gradients and the gradient into the
    hidden layer (none flows into the input)."""
    first = model["in_dim"] * model["hidden"]
    second = model["hidden"] * model["n_classes"]
    forward = 2 * (first + second)
    backward = 2 * first + 2 * 2 * second
    return forward + backward
