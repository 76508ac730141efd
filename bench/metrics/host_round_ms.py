"""Host time per round inside ``round`` that is not a wait on the device:
the program's ``repro.round`` span less the ``repro.sync`` spans inside
it, mean per round (``bench.trace_spans``). Not enrolled: it reads
``facts.program``, which the harness sets only once it installs an
annotating ``Telemetry`` for the traced window (PERF.md, Open questions)."""
UNIT = "ms"


def read(facts):
    prog = getattr(facts, "program", None)
    rnd = prog.span_s.get("round") if prog is not None else None
    if not rnd:
        return None
    return 1e3 * (rnd["total"] - prog.round_sync_s) / rnd["count"]
