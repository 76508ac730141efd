"""Device time per round under the round program's ``aggregate`` scope:
the segment-sum, the intra-group step, the inter-group step, the global
mean and the group-delta flatten (``bench.trace_spans``). Not enrolled:
it reads ``facts.program``, which the harness does not set yet (PERF.md,
Open questions)."""
from bench.trace_spans import per_round_ms

UNIT = "ms"


def read(facts):
    return per_round_ms(getattr(facts, "program", None), facts.rounds,
                        ("aggregate",))
