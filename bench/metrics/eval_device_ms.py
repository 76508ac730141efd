"""Device time per eval of the eval programs, from the trace. The pinned
grouped eval is jitted from a function named ``fn``
(``grouped_eval_correct``), so its trace name is ``jit_fn``; the streamed
eval runs, block by block, the program jitted from ``_correct_one``'s
``one``: ``jit_one``."""
from bench.trace_reduce import program_seconds

UNIT = "ms"


def read(facts):
    s = program_seconds(facts.trace, r"jit_fn|jit_one") if facts.trace else None
    if s is None or facts.evals == 0:
        return None
    return 1e3 * s / facts.evals
