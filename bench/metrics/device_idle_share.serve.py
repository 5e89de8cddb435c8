"""Share of the traced window in which no operation ran on the device,
in % (1 - busy union / window), in the served cell. Moves
``latency_p95_ms``."""
UNIT = "%"


def read(run):
    share = run.trace.idle_share()
    return None if share is None else 100.0 * share
