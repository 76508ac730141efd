"""Model kinds: ``<kind>.py`` for each ``config["model"]["kind"]``, found
by its name (``load``). A kind module has

  program(model, data)   the program's ``ModelSpec`` (the only function
                         that imports the program, and lazily)
  row_shape(model, data) the shape of one client row, as the pinned and
                         streamed client stores hold it
  apply(p, x)            the reference forward pass, plain ``jax.numpy``
                         over the parameter pytree, no import of the
                         program
  train_flops_per_sample(model, data)
                         forward and backward operations of one row

``model`` and ``data`` are the configuration's ``model`` and ``data``
blocks. Adding a kind adds a module here and touches nothing else."""
import importlib


def load(config: dict):
    """The kind module of a configuration."""
    return importlib.import_module(f"bench.models.{config['model']['kind']}")
