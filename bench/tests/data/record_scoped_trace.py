#!/usr/bin/env python3
"""Record the small chip trace of the program's named scopes and spans.

    python3 bench/tests/data/record_scoped_trace.py OUT_DIR

On one TPU, inside a ``bench:window`` span: one 64-key sweep with parents
through ``LaneEngine.sweep`` over a Graph 500 graph of scale 12 in a
``bench:sweep`` span, a 20 ms host pause in a ``bench:wait`` span, then
eight ticks of an ``AnalyticsService`` (64 lanes, 256 slots) serving 96
one-key BFS requests in a ``bench:serve`` span, traced with the host
tracer at level 1. The drain and the service's step carry the engine's
named scopes (``core/packed.py STEP_SCOPES``), the ticks the service's
``repro:service.*`` spans. Every program runs once before the trace. The
``.xplane.pb`` lands under OUT_DIR; ``tiny_scoped.xplane.pb`` beside this
script is one such file.
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
                               np.asarray(arrays[1]))
    eng = p.LaneEngine(g, lanes=64)
    jax.block_until_ready(eng.sweep(keys[:64], derive_parents=True).parent)
    svc = p.AnalyticsService(g, p.ServiceConfig(lanes=64, slots=256,
                                                streaming=True))
    svc.warmup()
    for i in range(4):
        svc.submit(p.AnalyticsRequest(query=p.BFSQuery(
            sources=(int(keys[i]),)), id=f"warm{i}"))
    while svc.busy():
        svc.step()
    for i in range(96):
        svc.submit(p.AnalyticsRequest(query=p.BFSQuery(
            sources=(int(keys[64 + i]),)), id=f"q{i}"))

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("bench:sweep"):
            r = eng.sweep(keys[:64], derive_parents=True)
            jax.block_until_ready((r.depth, r.parent))
        with jax.profiler.TraceAnnotation("bench:wait"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench:serve"):
            for _ in range(8):
                svc.step()
    jax.profiler.stop_trace()
    print("bu_fallback_passes", int(r.bu_fallback_passes))


if __name__ == "__main__":
    main(sys.argv[1])
