"""95th percentile (nearest rank) of the wait in the service's queue, in
ms: host clock, from a request's due time to the end of the first
``step()`` after which it was running (``serving/service.py _dispatch``
and the epoch recycle). Moves ``latency_p95_ms``."""
from bench.lib.stats import percentile

UNIT = "ms"


def read(run):
    wait = run.facts.get("queue_wait_ms")
    return percentile(wait, 95) if wait else None
