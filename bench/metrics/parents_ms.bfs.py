"""Device milliseconds per sweep of the parent derivation
(``core/msbfs.py _derive_parents``).

Read from the ``XLA Modules`` line of the trace, per sweep of the traced
window. Moves ``teps``.
"""
UNIT = "ms"
MODULES = {"host": ("jit__derive_parents",)}


def read(run):
    sweeps = run.facts.get("sweeps", 0)
    seconds = run.trace.module_seconds(MODULES["host"])
    if not sweeps or seconds <= 0:
        return None
    return 1e3 * seconds / sweeps
