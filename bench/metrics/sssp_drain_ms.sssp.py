"""Device milliseconds per sweep of the SSSP engine's traversal: the
delta-stepping drain of ``traversal/sssp.py`` (``_sssp_drain``) over the
light and heavy relaxation passes.

Read from the ``XLA Modules`` line of the trace, per sweep of the traced
window. Moves ``teps``.
"""
UNIT = "ms"
MODULES = ("jit__sssp_drain",)


def read(run):
    sweeps = run.facts.get("sweeps", 0)
    seconds = run.trace.module_seconds(MODULES)
    if not sweeps or seconds <= 0:
        return None
    return 1e3 * seconds / sweeps
