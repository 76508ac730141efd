"""Host time per round in the program's ``repro.sync`` spans: the reads
of round and eval results, each a wait for the device
(``bench.trace_spans``). Not enrolled: it reads ``facts.program``, which
the harness does not set yet (PERF.md, Open questions)."""
UNIT = "ms"


def read(facts):
    prog = getattr(facts, "program", None)
    if prog is None or "round" not in prog.span_s:
        return None
    sync = prog.span_s.get("sync", {"total": 0.0})["total"]
    return 1e3 * sync / prog.span_s["round"]["count"]
