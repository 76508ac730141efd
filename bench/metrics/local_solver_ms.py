"""Device time per round under the round program's ``solver`` scope: the
parameter gather and the vmapped local solver (``bench.trace_spans``).
Not enrolled: it reads ``facts.program``, which the harness does not set
yet (PERF.md, Open questions)."""
from bench.trace_spans import per_round_ms

UNIT = "ms"


def read(facts):
    return per_round_ms(getattr(facts, "program", None), facts.rounds,
                        ("solver",))
