"""Host wall time per round in the newcomer cold start (eq. 9,
``FedGroupTrainer.client_cold_start``), timed by the benchmark around the
trainer it built; streamed feeding only."""
UNIT = "ms"


def read(facts):
    if not facts.spans or facts.rounds == 0:
        return None
    return 1e3 * facts.spans["cold_start"] / facts.rounds
