"""Mean host milliseconds of one service tick (``AnalyticsService.step``:
the pool's step, its read-out and the answer collection), over the ticks
that start in the window. Moves ``latency_p95_ms``."""
UNIT = "ms"


def read(run):
    ticks = run.facts.get("tick_ms")
    return sum(ticks) / len(ticks) if ticks else None
