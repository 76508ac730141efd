"""Host wall time per round in ``Population.next_cohort`` (the wait for
the prefetched cohort), timed by the benchmark around the population it
built; streamed feeding only."""
UNIT = "ms"


def read(facts):
    if not facts.spans or facts.rounds == 0:
        return None
    return 1e3 * facts.spans["next_cohort"] / facts.rounds
