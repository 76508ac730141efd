"""Backend compiles plus persistent-cache loads inside the window, from
``jax.monitoring``: programs the set-up did not warm."""
UNIT = "count"


def read(facts):
    return facts.compiles_in_window
