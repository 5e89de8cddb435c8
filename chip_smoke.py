#!/usr/bin/env python3
"""Run the main path once on a TPU, through the entry points users call,
and check every answer against the repository's references.

Default phase (one chip, one process), in this order:

1. refuse to start unless JAX's first device is a TPU;
2. build the Graph500 graph ``rmat_weighted_graph(20, 16, seed)``:
   n = 1,048,576 vertices, about 33.55M directed edge slots;
3. batched Graph500 BFS: 64 search keys in one lane-engine sweep
   (``run_graph500(batched=True, lanes=64)``). The BFS trees of 8 keys
   are validated, and their depths and parents compared with
   ``bfs_reference``;
4. delta-stepping SSSP from 4 keys (``LaneEngine.sssp_sweep`` at bucket
   width ``SSSP_DELTA``); 2 of them are compared with
   ``dijkstra_reference``;
5. the served path: an ``AnalyticsService`` with streaming read-outs
   replays 32 requests of the mix bfs:3,khop:2,reach:1,sssp:1. Every
   answer is compared with its lane of ``run_query`` over all requests of
   its kind, and one of each kind with the NumPy references;
6. the Pallas kernels: each is compiled for the chip (``interpret=False``)
   at the graph's size, then smaller. One that compiles runs and is
   compared with its ``*_ref``. For one that is refused, the engine's
   ``probe_impl="pallas"`` path must fail with the compiler's reason.

``--four-chips`` runs only the sharded engines, in one process on four
chips: the 1-D engine (``run_graph500(batched=True, ndev=4)``) and the 2x2
grid (``LaneEngine(grid=(2, 2))``, BFS and SSSP), each compared bit for
bit with the host engine on device 0.

Each phase prints its compile seconds, its timed seconds and the device's
``peak_bytes_in_use`` on labelled lines. The last line is one JSON object
naming the device. A failed check raises, so the script then exits
non-zero without that line.

    python chip_smoke.py [--four-chips] [--seed S]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SCALE, EDGEFACTOR = 20, 16
NUM_KEYS = 64
VALIDATED_KEYS = 8
SSSP_SOURCES, DIJKSTRA_SOURCES = 4, 2
# the four-chip path is charged four chips' time: fewer SSSP lanes there
GRID_SSSP_SOURCES = 2
SERVED_REQUESTS = 32
SERVED_MIX = "bfs:3,khop:2,reach:1,sssp:1"
# the tolerance tests/test_traversal.py holds SSSP distances to
DIST_ATOL = 1e-4
# SSSP bucket width: the weights' upper bound, so every edge is light and a
# lane takes about as many steps as its shortest paths have hops (about 25
# at scale 20, against about 130 at the default max_w / avg_degree width).
# A step costs the same O(m * lanes) relax either way.
SSSP_DELTA = 1.0
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing answer."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


_START = time.perf_counter()


def report(phase: str, **fields) -> None:
    fields["elapsed_seconds"] = time.perf_counter() - _START
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling programs, and
    the number of programs handed to the backend compiler, read from
    ``jax.monitoring`` events."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0

    def _record(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.programs += event == _COMPILE_EVENTS[-1]

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._record)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._record)

    def since(self, mark: tuple[float, int]) -> tuple[float, int]:
        return self.seconds - mark[0], self.programs - mark[1]

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.programs


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` of each device, as the backend reports it."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use", "not reported"))
    return out


class References:
    """Host copies of the graph and memoised NumPy reference answers."""

    def __init__(self, wg):
        from repro.traversal.ref import to_numpy_weighted
        self.row_ptr, self.col_idx, self.weights = to_numpy_weighted(wg)
        self._bfs: dict[int, tuple] = {}
        self._dijkstra: dict[int, np.ndarray] = {}

    def bfs(self, root: int) -> tuple[np.ndarray, np.ndarray]:
        """(parent, depth) of ``bfs_reference``."""
        from repro.core.ref import bfs_reference
        if root not in self._bfs:
            self._bfs[root] = bfs_reference(self.row_ptr, self.col_idx, root)
        return self._bfs[root]

    def dijkstra(self, root: int) -> np.ndarray:
        from repro.traversal.ref import dijkstra_reference
        if root not in self._dijkstra:
            self._dijkstra[root] = dijkstra_reference(
                self.row_ptr, self.col_idx, self.weights, root)
        return self._dijkstra[root]

    def check_dist(self, root: int, got: np.ndarray, what: str) -> None:
        ref = self.dijkstra(root)
        got = np.asarray(got, np.float64)
        check(np.array_equal(np.isfinite(got), np.isfinite(ref)),
              f"{what}: reached set of source {root} differs from Dijkstra")
        fin = np.isfinite(ref)
        err = float(np.max(np.abs(got[fin] - ref[fin]), initial=0.0))
        check(err <= DIST_ATOL,
              f"{what}: source {root} distances off Dijkstra by {err}")


def build_graph(seed: int):
    from repro.graph.generator import rmat_weighted_graph
    t0 = time.perf_counter()
    wg = rmat_weighted_graph(SCALE, EDGEFACTOR, seed)
    report("graph", scale=SCALE, edgefactor=EDGEFACTOR, n=wg.n, m=wg.m,
           build_seconds=time.perf_counter() - t0)
    return wg


def phase_bfs(wg, refs: References, clock: CompileClock, seed: int,
              devices) -> np.ndarray:
    """Batched Graph500 BFS over 64 keys; returns the keys."""
    from repro.graph.graph500 import run_graph500
    from repro.graph.validate import validate_bfs_tree
    mark = clock.mark()
    res = run_graph500(SCALE, EDGEFACTOR, batched=True, num_roots=NUM_KEYS,
                       lanes=NUM_KEYS, graph=wg.csr, seed=seed)
    compile_s, programs = clock.since(mark)
    sweep = res.sweep
    roots = np.asarray(res.roots)
    layers = np.asarray(sweep.num_layers)
    check(roots.size == NUM_KEYS and layers.size == NUM_KEYS
          and (layers > 0).all(), "bfs: not every search key was answered")
    parent = np.asarray(sweep.parent)
    depth = np.asarray(sweep.depth)
    checked = list(range(0, NUM_KEYS, NUM_KEYS // VALIDATED_KEYS))
    for lane in checked:
        root = int(roots[lane])
        validate_bfs_tree(refs.row_ptr, refs.col_idx, parent[:, lane], root)
        ref_parent, ref_depth = refs.bfs(root)
        check(np.array_equal(depth[:, lane], ref_depth),
              f"bfs: depths of key {root} differ from bfs_reference")
        check(np.array_equal(parent[:, lane], ref_parent),
              f"bfs: parents of key {root} differ from bfs_reference")
    report("bfs", keys=roots.size, lanes=res.lanes,
           validated_keys=len(checked), compile_seconds=compile_s,
           programs_compiled=programs, sweep_seconds=res.times[0],
           aggregate_teps=res.aggregate_teps,
           max_layers=int(layers.max()),
           peak_bytes_in_use=peak_bytes(devices)[0])
    return roots


def phase_sssp(wg, refs: References, clock: CompileClock, sources,
               devices) -> None:
    import jax

    from repro.analytics.engine import LaneEngine
    eng = LaneEngine(wg)
    mark = clock.mark()
    jax.block_until_ready(eng.sssp_sweep(sources, SSSP_DELTA).dist)
    compile_s, programs = clock.since(mark)
    t0 = time.perf_counter()
    out = eng.sssp_sweep(sources, SSSP_DELTA)
    jax.block_until_ready(out.dist)
    sweep_s = time.perf_counter() - t0
    check(not np.asarray(out.truncated).any(),
          "sssp: a lane hit the step cap")
    dist = np.asarray(out.dist)
    for i in range(DIJKSTRA_SOURCES):
        refs.check_dist(int(sources[i]), dist[:, i], "sssp")
    report("sssp", sources=len(sources), checked_against_dijkstra=
           DIJKSTRA_SOURCES, compile_seconds=compile_s,
           programs_compiled=programs, sweep_seconds=sweep_s,
           max_steps=int(np.asarray(out.steps).max()),
           peak_bytes_in_use=peak_bytes(devices)[0])


def _batched_queries(trace) -> dict:
    """kind -> (one query over the sources of all that kind's requests,
    {request id: (lane, target columns)}). Each served request has one
    source; reach targets are concatenated in request order."""
    from repro.analytics.api import (BFSQuery, KHopQuery, ReachQuery,
                                     SSSPQuery)
    by_kind: dict[str, list] = {}
    for env in trace:
        check(len(env.query.sources) == 1,
              f"served: request {env.id} has several sources")
        by_kind.setdefault(env.query.kind, []).append(env)
    out = {}
    for kind, envs in by_kind.items():
        sources = tuple(e.query.sources[0] for e in envs)
        lanes, targets = {}, []
        for lane, e in enumerate(envs):
            t = ()
            if kind == "reach":
                t = tuple(e.query.sources if e.query.targets is None
                          else e.query.targets)
            lanes[e.id] = (lane, np.arange(len(targets),
                                           len(targets) + len(t)))
            targets.extend(t)
        if kind == "bfs":
            q = BFSQuery(sources=sources)
        elif kind == "khop":
            ks = {e.query.k for e in envs}
            check(len(ks) == 1, "served: khop requests differ in k")
            q = KHopQuery(sources=sources, k=ks.pop())
        elif kind == "reach":
            q = ReachQuery(sources=sources, targets=tuple(targets))
        else:
            deltas = {e.query.delta for e in envs}
            check(len(deltas) == 1, "served: sssp requests differ in delta")
            q = SSSPQuery(sources=sources, delta=deltas.pop())
        out[kind] = (q, lanes)
    return out


def _same_answer(kind: str, got, ref, lane: int, cols) -> bool:
    """Is the one-source answer ``got`` lane ``lane`` of the batched
    answer ``ref``?"""
    if kind == "bfs":
        return (np.array_equal(got.depth[:, 0], ref.depth[:, lane])
                and got.num_layers[0] == ref.num_layers[lane]
                and got.reached[0] == ref.reached[lane])
    if kind == "khop":
        return (np.array_equal(got.members(0), ref.members(lane))
                and got.counts[0] == ref.counts[lane])
    if kind == "reach":
        return np.array_equal(got.hops[0], ref.hops[lane, cols])
    return (np.array_equal(got.dist[:, 0], ref.dist[:, lane])
            and got.steps[0] == ref.steps[lane])


def _check_one_of_each_kind(answers: dict, refs: References) -> None:
    """The largest answer of each kind against the NumPy references."""
    for kind in ("bfs", "khop", "reach", "sssp"):
        check(answers.get(kind), f"served: the trace has no {kind} request")
    q, res = max(answers["bfs"], key=lambda a: int(a[1].reached[0]))
    check(np.array_equal(res.depth[:, 0], refs.bfs(q.sources[0])[1]),
          "served: bfs depths differ from bfs_reference")
    q, res = max(answers["khop"], key=lambda a: int(a[1].counts[0]))
    d = refs.bfs(q.sources[0])[1]
    check(np.array_equal(res.members(0), np.flatnonzero((d >= 0)
                                                        & (d <= q.k))),
          "served: khop members differ from bfs_reference")
    q, res = answers["reach"][0]
    d = refs.bfs(q.sources[0])[1]
    check(np.array_equal(res.hops[0], d[np.asarray(q.targets)]),
          "served: reach hops differ from bfs_reference")
    q, res = max(answers["sssp"],
                 key=lambda a: int(np.isfinite(a[1].dist[:, 0]).sum()))
    refs.check_dist(int(q.sources[0]), res.dist[:, 0], "served sssp")


def phase_served(wg, refs: References, clock: CompileClock, seed: int,
                 devices) -> None:
    from repro.analytics.api import run_query
    from repro.serving import AnalyticsService, ServiceConfig
    from repro.serving.admission import DONE
    from repro.serving.trace import synthetic_trace
    svc = AnalyticsService(wg, ServiceConfig(slots=SERVED_REQUESTS,
                                             sssp_slots=8, delta=SSSP_DELTA,
                                             streaming=True))
    mark = clock.mark()
    svc.warmup()
    compile_s, programs = clock.since(mark)
    trace = synthetic_trace(wg.n, SERVED_REQUESTS, mix=SERVED_MIX, seed=seed,
                            delta=SSSP_DELTA)
    mark = clock.mark()
    t0 = time.perf_counter()
    stats = svc.replay(trace)
    replay_s = time.perf_counter() - t0
    replay_compile_s, replay_programs = clock.since(mark)
    check(stats["done"] == len(trace) and stats["rejected"] == 0,
          f"served: {stats['done']} of {len(trace)} requests answered")
    # run_query answers each kind's requests in one sweep: a lane's result
    # does not depend on the sweep's other lanes
    t0 = time.perf_counter()
    expected = {kind: (run_query(svc.engine, q), lanes)
                for kind, (q, lanes) in _batched_queries(trace).items()}
    run_query_s = time.perf_counter() - t0
    answers: dict[str, list] = {}
    for env in trace:
        rec = svc.record(env.id)
        check(rec.status == DONE, f"served: request {env.id} {rec.status}")
        got = rec.answer.result
        ref, lanes = expected[rec.kind]
        check(_same_answer(rec.kind, got, ref, *lanes[env.id]),
              f"served: {rec.kind} answer {env.id} differs from run_query")
        answers.setdefault(rec.kind, []).append((env.query, got))
    _check_one_of_each_kind(answers, refs)
    report("served", requests=len(trace),
           per_kind=",".join(f"{k}:{len(v)}" for k, v in
                             sorted(answers.items())),
           answered_early=stats["answered_early"], layers=stats["layers"],
           warmup_compile_seconds=compile_s, warmup_programs=programs,
           replay_seconds=replay_s,
           replay_compile_seconds=replay_compile_s,
           replay_programs=replay_programs,
           run_query_seconds=run_query_s,
           peak_bytes_in_use=peak_bytes(devices)[0])


def _kernel_cases(g, seed: int) -> dict:
    """name -> (pallas entry, reference, arguments, static keywords), with
    arguments built from the CSR ``g`` and random lane state."""
    import jax.numpy as jnp

    from repro import kernels as K
    from repro.core.csr import ell_pad
    rng = np.random.default_rng(seed)
    n, m = g.n, g.m
    starts, deg = g.row_ptr[:-1], g.deg

    def words(*shape):
        return jnp.asarray(rng.integers(0, 1 << 32, shape, dtype=np.uint64)
                           .astype(np.uint32))

    neigh, valid = ell_pad(g, 16)
    plane = -(-n // 32)
    return {
        "msbfs_probe": (K.msbfs_probe_pallas, K.msbfs_probe_ref,
                        (starts, deg, words(n, 2), g.col_idx, words(n, 2)),
                        dict(max_pos=8)),
        "bottom_up_probe": (
            K.bottom_up_probe_pallas, K.bottom_up_probe_ref,
            (starts, deg, jnp.asarray(rng.integers(0, 2, n), jnp.int32),
             jnp.full((n,), -1, jnp.int32), g.col_idx, words(plane)),
            dict(max_pos=8)),
        "semiring_relax": (
            K.semiring_relax_pallas, K.semiring_relax_ref,
            (starts, deg, g.col_idx,
             jnp.asarray(rng.random(m), jnp.float32),
             jnp.asarray(rng.random((n, 8)), jnp.float32)),
            dict(max_pos=8)),
        "topdown_scan": (K.topdown_scan_pallas, K.topdown_scan_ref,
                         (g.src_idx, g.col_idx, words(plane), words(plane)),
                         dict(n=n)),
        "ell_spmm": (K.ell_spmm_pallas, K.ell_spmm_ref,
                     (neigh, valid.astype(jnp.int32),
                      jnp.asarray(rng.random((n, 128)), jnp.float32)), {}),
    }


def _refusal(e: Exception) -> str:
    """The compiler's reason, without the shapes it names."""
    return f"{type(e).__name__}: {str(e).splitlines()[0].split('. ')[0]}"


def _compiler_refusals():
    """What the Pallas TPU lowering and the TPU compiler raise for a
    kernel they refuse."""
    import jax
    return ValueError, NotImplementedError, jax.errors.JaxRuntimeError


def _engine_pallas_error(wg) -> dict:
    """The refusal each engine path with ``probe_impl="pallas"`` raises, or
    None where it ran (and then matched the XLA path)."""
    from repro.analytics.engine import LaneEngine
    from repro.graph.generator import sample_roots
    roots = sample_roots(wg.csr, 4, seed=1)
    runs = {
        "msbfs_probe": (lambda impl: LaneEngine(wg.csr, probe_impl=impl)
                        .sweep(roots).depth),
        "semiring_relax": (lambda impl: LaneEngine(wg, probe_impl=impl)
                           .sssp_sweep(roots).dist),
    }
    out = {}
    for name, run in runs.items():
        try:
            got = np.asarray(run("pallas"))
        except _compiler_refusals() as e:
            out[name] = _refusal(e)
            continue
        check(np.array_equal(got, np.asarray(run("xla"))),
              f"kernels: the {name} engine path differs from the XLA path")
        out[name] = None
    return out


def phase_kernels(wg, seed: int, devices) -> None:
    """Compile every Pallas kernel for the chip at the graph's scale, then
    at smaller ones; run and check the ones the compiler accepts."""
    import functools

    import jax

    from repro.graph.generator import rmat_weighted_graph
    graphs = {SCALE: wg}
    refused: dict[str, dict[int, str]] = {}
    accepted: dict[str, int] = {}
    for scale in (SCALE, 16, 12, 10):
        if scale not in graphs:
            graphs[scale] = rmat_weighted_graph(scale, EDGEFACTOR, seed)
        cases = _kernel_cases(graphs[scale].csr, seed)
        for name, (fn, ref, args, static) in cases.items():
            if name in accepted:
                continue
            call = jax.jit(functools.partial(fn, interpret=False, **static))
            try:
                compiled = call.lower(*args).compile()
            except _compiler_refusals() as e:
                refused.setdefault(name, {})[scale] = _refusal(e)
                continue
            got = jax.tree.leaves(compiled(*args))
            want = jax.tree.leaves(ref(*args, **static))
            for a, b in zip(got, want):
                check(np.allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                  atol=1e-6),
                      f"kernels: {name} at scale {scale} differs from ref")
            accepted[name] = scale
            report("kernel", name=name, accepted_at_scale=scale,
                   matches_ref=True)
    never = {k: v for k, v in refused.items() if k not in accepted}
    for name, why in sorted(never.items()):
        report("kernel", name=name,
               refused_at_scales=",".join(map(str, why)),
               reasons=repr(sorted(set(why.values()))))
    # the engine hands a kernel other lane widths than the cases above, so
    # its refusal may be another kernel's reason, but never a new one
    reasons = {r for why in refused.values() for r in why.values()}
    for name, err in _engine_pallas_error(graphs[min(graphs)]).items():
        if err is None:
            check(name in accepted, f"kernels: {name} ran in the engine but "
                  f"did not compile alone")
            report("engine_pallas", kernel=name, ran=True)
        else:
            check(err in reasons,
                  f"kernels: the {name} engine path failed with {err!r}, "
                  f"not with a compiler refusal")
            report("engine_pallas", kernel=name, fails_loudly=repr(err))
    report("kernels", accepted=len(accepted), refused=len(never),
           peak_bytes_in_use=peak_bytes(devices)[0])


def _same_sweep(a, b, what: str) -> None:
    n = b.depth.shape[0]
    for field in ("depth", "parent", "num_layers", "edges_traversed"):
        x, y = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        if field in ("depth", "parent"):
            x = x[:n]
        check(np.array_equal(x, y), f"{what}: {field} differs from the "
              f"host engine")


def phase_four_chips(wg, clock: CompileClock, seed: int, devices) -> None:
    """The 1-D and 2x2-grid sharded engines against the host engine."""
    import jax

    from repro.analytics.engine import LaneEngine
    from repro.core.dist2d import mesh2d
    from repro.core.dist_msbfs import host_mesh
    from repro.graph.graph500 import run_graph500
    for name, mesh in (("1-D", host_mesh(4)), ("2x2", mesh2d(2, 2))):
        check(len({d.id for d in mesh.devices.flat}) == 4,
              f"four-chips: the {name} mesh does not span four devices")
    # one sweep per engine, so each timed sweep includes its compilation
    kw = dict(batched=True, num_roots=NUM_KEYS, lanes=NUM_KEYS,
              graph=wg.csr, seed=seed, warmup=False)
    mark = clock.mark()
    host = run_graph500(SCALE, EDGEFACTOR, **kw)
    report("host", compile_seconds=clock.since(mark)[0],
           seconds_with_compile=host.times[0])
    mark = clock.mark()
    dist = run_graph500(SCALE, EDGEFACTOR, ndev=4, **kw)
    _same_sweep(dist.sweep, host.sweep, "1-D ndev=4")
    report("dist_msbfs", ndev=4, bit_identical=True,
           compile_seconds=clock.since(mark)[0],
           seconds_with_compile=dist.times[0])

    roots = np.asarray(host.roots)
    grid, local = LaneEngine(wg, grid=(2, 2)), LaneEngine(wg)
    mark = clock.mark()
    t0 = time.perf_counter()
    got = grid.sweep(roots, derive_parents=True)
    jax.block_until_ready(got.depth)
    grid_s = time.perf_counter() - t0
    # the host sweep above answered the same keys; a lane's answer does not
    # depend on the width of the lane pool
    _same_sweep(got, host.sweep, "2x2 grid")
    report("dist2d", grid="2x2", keys=roots.size, bit_identical=True,
           compile_seconds=clock.since(mark)[0],
           seconds_with_compile=grid_s)
    sources = roots[:GRID_SSSP_SOURCES]
    mark = clock.mark()
    t0 = time.perf_counter()
    got = grid.sssp_sweep(sources, SSSP_DELTA)
    jax.block_until_ready(got.dist)
    grid_s = time.perf_counter() - t0
    want = local.sssp_sweep(sources, SSSP_DELTA)
    for field in ("dist", "steps", "truncated"):
        check(np.array_equal(np.asarray(getattr(got, field))[:wg.n],
                             np.asarray(getattr(want, field))),
              f"2x2 grid sssp: {field} differs from the host engine")
    report("dist2d_sssp", grid="2x2", sources=len(sources),
           bit_identical=True, compile_seconds=clock.since(mark)[0],
           seconds_with_compile=grid_s,
           peak_bytes_in_use=peak_bytes(devices))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1-D and 2x2 sharded engines on four "
                         "chips, against the host engine")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph, its weights and the trace")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found {len(devices)} "
                 f"{d0.platform} device(s)")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: needs {need} TPU devices, found "
                 f"{len(devices)}")
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    report("device", platform=d0.platform, device_kind=repr(d0.device_kind),
           count=len(devices), compile_cache=cache)

    t0 = time.perf_counter()
    with CompileClock() as clock:
        wg = build_graph(args.seed)
        if args.four_chips:
            phase_four_chips(wg, clock, args.seed, devices[:4])
        else:
            refs = References(wg)
            roots = phase_bfs(wg, refs, clock, args.seed, devices)
            phase_sssp(wg, refs, clock, roots[:SSSP_SOURCES], devices)
            phase_served(wg, refs, clock, args.seed, devices)
            phase_kernels(wg, args.seed, devices)
    report("total", seconds=time.perf_counter() - t0,
           compile_seconds=clock.seconds, programs_compiled=clock.programs)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
