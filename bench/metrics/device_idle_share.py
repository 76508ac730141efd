"""Share of the traced window in which no operation ran on the device
(1 - the union of operation intervals over the window), mean per chip."""
UNIT = "%"


def read(facts):
    if facts.trace is None:
        return None
    return 100.0 * facts.trace.idle_share
