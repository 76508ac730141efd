"""Device time per round under the round program's ``mean_loss`` and
``discrepancy`` scopes: the second forward pass and the eq.-4
discrepancy, work beyond the paper's update (``bench.trace_spans``). Not
enrolled: it reads ``facts.program``, which the harness does not set yet
(PERF.md, Open questions)."""
from bench.trace_spans import per_round_ms

UNIT = "ms"


def read(facts):
    return per_round_ms(getattr(facts, "program", None), facts.rounds,
                        ("mean_loss", "discrepancy"))
