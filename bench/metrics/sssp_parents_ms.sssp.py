"""Device milliseconds per sweep of the SSSP parent derivation
(``traversal/sssp.py _sssp_parents``).

Read from the ``XLA Modules`` line of the trace, per sweep of the traced
window. Moves ``teps``.
"""
UNIT = "ms"
MODULES = ("jit__sssp_parents",)


def read(run):
    sweeps = run.facts.get("sweeps", 0)
    seconds = run.trace.module_seconds(MODULES)
    if not sweeps or seconds <= 0:
        return None
    return 1e3 * seconds / sweeps
