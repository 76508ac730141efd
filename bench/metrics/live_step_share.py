"""Share of the local solver's dispatched steps that are live: the
window's increase of the program's ``solver.steps_live`` counter over
that of ``solver.steps_run`` (both from host-side client sizes). Not
enrolled: it reads ``facts.counters``, the window's counter increases,
which the harness does not set yet (PERF.md, Open questions)."""
UNIT = "%"


def read(facts):
    counters = getattr(facts, "counters", None) or {}
    run = counters.get("solver.steps_run")
    if not run:
        return None
    return 100.0 * counters["solver.steps_live"] / run
