"""Backend compile seconds during set-up, from ``jax.monitoring``
(programs loaded from the persistent cache do not count)."""
UNIT = "s"


def read(facts):
    return facts.compile_s
