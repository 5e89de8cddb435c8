"""95th percentile (nearest rank) of how late the open-loop client
submitted a request due in the window, in ms: host clock, submit time
minus due time. Moves ``latency_p95_ms``."""
from bench.lib.stats import percentile

UNIT = "ms"


def read(run):
    lag = run.facts.get("client_lag_ms")
    return percentile(lag, 95) if lag else None
