"""Run one cell of ``BENCHMARK.json`` and build its result line.

Everything is found by name: the cell's entry in ``BENCHMARK.json`` names
its configuration and traffic; ``bench/configs/<config>.json`` holds the
configuration, ``bench/workloads/<cell>.json`` the traffic and the driver
(``bench/drivers/<driver>.py``), and each per-layer metric is read by
``bench/metrics/<metric>.py``. A new configuration, traffic mix or metric
is new files plus new entries; no file here changes.

A run: set-up (``setup_s`` runs from process start to the end of it), the
window (traced into ``bench/traces/`` under ``--trace 1``), the device's
memory peak, then the check of every answer of the window against the
plain references, and the metrics of the cell.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass

from bench.drivers import load as load_driver
from bench.lib import device as chip
from bench.lib import xplane
from bench.lib.peaks import peaks_for

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(BENCH_DIR, "traces")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    workload: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: str = ROOT
    seconds: float = 0.0


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[entry["config"]]["file"]))
    workload = _json(os.path.join(root, "bench", "workloads",
                                  f"{name}.json"))
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise ValueError(f"bench/workloads/{name}.json says {key} = "
                             f"{workload[key]!r}, BENCHMARK.json "
                             f"{entry[key]!r}")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=entry["chips"], config=config,
                workload=workload, end_to_end=e2e, per_layer=per_layer,
                root=root)


def metric_reader(name: str, root: str = ROOT):
    """The module ``bench/metrics/<name>.py`` (its ``read(run)``)."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Spans:
    """The benchmark's host spans: kept as (name, start, end) on the host
    clock, and written into the profiler's trace while it records."""

    def __init__(self, tracing: bool):
        self.tracing = tracing
        self.spans: list[tuple[str, float, float]] = []

    @contextmanager
    def __call__(self, name: str):
        annotation = None
        if self.tracing:
            import jax
            annotation = jax.profiler.TraceAnnotation(
                xplane.SPAN_PREFIX + name)
            annotation.__enter__()
        start = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, start, time.perf_counter()))
            if annotation is not None:
                annotation.__exit__(None, None, None)


def _brief(facts: dict) -> dict:
    """The driver's facts with long lists cut to their first ten."""
    return {k: (v[:10] if isinstance(v, list) else v)
            for k, v in facts.items()}


@dataclass
class RunView:
    """What a per-layer metric reads: the reduced trace, the driver's
    host-clock facts, the chip's peaks and the cell."""
    trace: xplane.Trace
    facts: dict
    peaks: dict
    cell: Cell


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             started: float, program=None, devices=None) -> dict:
    """Set up, run the window, check, and return the result line (a
    dict; ``checks`` comes last). ``devices`` None means the harness is
    rehearsed without a chip: the ``device`` record is then the CPU's."""
    import jax
    from bench.drivers import program as program_entries
    cell.seconds = float(seconds)
    counter = chip.CompileCounter().install()
    driver = load_driver(cell.workload["driver"])(
        cell, seed, program if program is not None else program_entries())
    driver.setup()
    setup_s = time.perf_counter() - started
    parts = " ".join(f"{k}={v:.3f}" for k, v in
                     getattr(driver, "setup_parts", {}).items())
    print(f"setup_s={setup_s:.3f} {parts}", file=sys.stderr, flush=True)
    spans = Spans(tracing=bool(trace))
    log_dir = os.path.join(TRACE_DIR, f"{cell.name}-{seed}")
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
    counter.active = True
    try:
        driver.window(float(seconds), spans)
    finally:
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
        counter.remove()
    used = devices if devices is not None else jax.devices()[:1]
    record = chip.describe(used)
    driver.free()
    t_check = time.perf_counter()
    checked = driver.check()
    print(f"check_s={time.perf_counter() - t_check:.3f} "
          f"facts={json.dumps(_brief(driver.facts()))}", file=sys.stderr,
          flush=True)
    numbers = checked["numbers"]
    correct = all(value <= limit for value, limit in numbers.values())

    metrics = {}
    breakdown = None
    if not trace:
        values = dict(driver.end_to_end(), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        reduced = xplane.load(xplane.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        view = RunView(trace=reduced, facts=driver.facts(),
                       peaks=(peaks_for(record["kind"])
                              if devices is not None else {}),
                       cell=cell)
        for m in cell.per_layer:
            value = metric_reader(m["name"], cell.root).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        record["busy_s"] = reduced.busy_s()
        record["window_s"] = reduced.window_s
        breakdown = {"device_ops": reduced.top_ops(10),
                     "idle_gaps": reduced.idle_gaps(10)}
    line = {"correct": correct, "attempted": checked["attempted"],
            "failed": checked["failed"], "metrics": metrics,
            "device": record}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compiles_in_window"] = counter.programs
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in numbers.items()}
    return line
