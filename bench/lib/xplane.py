"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers the
per-layer metrics read.

The trace holds one plane per TPU (``/device:TPU:<i>``) and one for the
host (``/host:CPU``). On a device plane, the ``XLA Modules`` line has one
event per executed program, named after the jitted function
(``jit__drain(12)``: name, then the program id), and the ``XLA Ops`` line
one event per executed operation. The benchmark marks its own host spans
with ``jax.profiler.TraceAnnotation`` under names that start with
``bench:``; ``bench:window`` bounds the measured window.

Everything is clipped to that window. Busy time is the union of the
operations' intervals on each device, averaged over devices.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"


def find_xplane(log_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def module_name(event_name: str) -> str:
    """``jit__drain(12)`` -> ``jit__drain``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _union(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Event:
    name: str
    start: float      # seconds on the trace's clock
    end: float


@dataclass
class Trace:
    """The events of one trace that fall inside its window."""
    window: tuple[float, float]
    ops: list[list[Event]]        # per device
    modules: list[list[Event]]    # per device
    spans: list[Event]            # the benchmark's host spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def devices(self) -> int:
        return len(self.ops)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        return sum(_union((e.start, e.end) for e in dev)
                   for dev in self.ops) / len(self.ops)

    def idle_share(self) -> float | None:
        """1 - busy / window, or None when no device op ran."""
        busy = self.busy_s()
        if busy <= 0 or self.window_s <= 0:
            return None
        return max(0.0, 1.0 - busy / self.window_s)

    def module_seconds(self, names) -> float:
        """Device seconds of the programs named ``names`` (by
        ``module_name``), averaged over devices."""
        names = set(names)
        if not self.modules:
            return 0.0
        return sum(sum(e.end - e.start for e in dev
                       if module_name(e.name) in names)
                   for dev in self.modules) / len(self.modules)

    def module_counts(self) -> dict[str, float]:
        """Device seconds per program name on device 0."""
        out: dict[str, float] = {}
        for e in (self.modules[0] if self.modules else []):
            k = module_name(e.name)
            out[k] = out.get(k, 0.0) + (e.end - e.start)
        return out

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` programs that took most device time, as
        [name, seconds] (device 0)."""
        items = sorted(self.module_counts().items(), key=lambda kv: -kv[1])
        return [[name, secs] for name, secs in items[:k]]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The ``k`` longest idle gaps of device 0 inside the window, each
        as [what the host was doing, seconds]. The host's doing is the
        innermost benchmark span covering the gap's middle."""
        if not self.ops:
            return []
        busy = _merged((e.start, e.end) for e in self.ops[0])
        gaps, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if self.window[1] > t:
            gaps.append((t, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) / 2
            inside = [sp for sp in self.spans
                      if sp.start <= mid <= sp.end and sp.name != WINDOW_SPAN]
            what = (min(inside, key=lambda sp: sp.end - sp.start).name
                    if inside else "outside any span")
            out.append([what, e - s])
        return out


def _events(line):
    for e in line.events:
        yield Event(e.name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9)


def _clip(events, lo, hi):
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` and clip it to its ``bench:window`` span."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, ops, modules = [], [], []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e.name.startswith(SPAN_PREFIX))
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops.append(list(_events(lines[OPS_LINE]))
                       if OPS_LINE in lines else [])
            modules.append(list(_events(lines[MODULES_LINE]))
                           if MODULES_LINE in lines else [])
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    lo, hi = windows[-1].start, windows[-1].end
    return Trace(window=(lo, hi),
                 ops=[_clip(d, lo, hi) for d in ops],
                 modules=[_clip(d, lo, hi) for d in modules],
                 spans=_clip(spans, lo, hi))
