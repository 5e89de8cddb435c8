#!/usr/bin/env python3
"""Record the small chip trace the trace-reduction test reads.

    python3 bench/tests/data/record_trace.py OUT_DIR

On one TPU: a Graph 500 graph of scale 12, one 64-key sweep with parents
through ``LaneEngine.sweep`` inside a ``bench:window`` span with a
``bench:sweep`` span, and a 20 ms host pause in a ``bench:wait`` span
between two such sweeps, traced with the host tracer at level 1. The
``.xplane.pb`` lands under OUT_DIR.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out_dir: str) -> None:
    import jax
    import numpy as np

    from bench.drivers import program
    from bench.lib import device, graphgen
    device.require_chips(1)
    p = program()
    arrays = graphgen.graph_for(
        {"structure_seed": 5, "scale": 12, "edgefactor": 16}, 5)[:3]
    g = p.CSRGraph(*arrays)
    keys = graphgen.search_keys(np.asarray(arrays[0]),
                               np.asarray(arrays[1]))[:64]
    eng = p.LaneEngine(g, lanes=64)
    jax.block_until_ready(eng.sweep(keys, derive_parents=True).parent)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:window"):
        for i in range(2):
            with jax.profiler.TraceAnnotation("bench:sweep"):
                r = eng.sweep(keys, derive_parents=True)
                jax.block_until_ready((r.depth, r.parent))
            if i == 0:
                with jax.profiler.TraceAnnotation("bench:wait"):
                    time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
