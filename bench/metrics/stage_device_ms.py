"""Device time per round of the cohort gather from the pinned stacks, the
program ``jit_gather_cohort`` (``fed.engine.gather_cohort``), from the
trace. None where no such program ran, as before the gather was one
jitted program."""
from bench.trace_reduce import program_seconds

UNIT = "ms"


def read(facts):
    s = (program_seconds(facts.trace, r"jit_gather_cohort")
         if facts.trace else None)
    if s is None or facts.rounds == 0:
        return None
    return 1e3 * s / facts.rounds
