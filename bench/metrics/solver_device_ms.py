"""Device time per round of the round-executor program (the local solver,
the aggregation and the round's loss), from the trace, by the program
name ``jit_round_fn``."""
from bench.trace_reduce import program_seconds

UNIT = "ms"


def read(facts):
    s = program_seconds(facts.trace, r"jit_round_fn") if facts.trace else None
    if s is None or facts.rounds == 0:
        return None
    return 1e3 * s / facts.rounds
