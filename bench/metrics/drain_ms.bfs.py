"""Device milliseconds per sweep of the engine's traversal: the fused
drain of ``core/msbfs.py`` over the packed steps of ``core/packed.py``.

Read from the ``XLA Modules`` line of the trace: the programs below, per
sweep of the traced window. Moves ``teps``.
"""
UNIT = "ms"
# the jitted traversal program of each engine (the host engine's drain)
MODULES = {"host": ("jit__drain",)}


def read(run):
    sweeps = run.facts.get("sweeps", 0)
    seconds = run.trace.module_seconds(MODULES["host"])
    if not sweeps or seconds <= 0:
        return None
    return 1e3 * seconds / sweeps
