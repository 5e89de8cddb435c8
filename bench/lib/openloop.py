"""Open-loop traffic: a schedule drawn from a seed, and the client that
offers it on the host clock.

A traffic file gives a rate, a lead-in, and a mix of request kinds with
their shares. ``make_schedule`` turns it into arrivals: exactly
``rate * seconds`` requests due inside the window, at times drawn
uniformly (a Poisson process given its count), so every seed offers the
same amount of work in another order. Requests due in the lead-in warm
the queue and are not counted. Each kind's count is its share of the
total, rounded by largest remainder.

``OpenLoopClient`` submits each request when it is due, whether or not
earlier ones are done, and steps the service whenever it has work. It
stamps, on the host clock, when each request was submitted, the end of
the first tick after which it was running, and the end of the tick after
which it was answered. A request due during a tick is submitted when the
tick ends: the service holds its lock while it steps.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

QUEUED, RUNNING, DONE, REJECTED = "queued", "running", "done", "rejected"


@dataclass
class Arrival:
    """One request of the schedule. ``due`` is seconds from the start of
    the window (negative in the lead-in)."""
    due: float
    kind: str
    params: dict
    in_window: bool
    submitted: float | None = None
    running: float | None = None
    done: float | None = None
    status: str = QUEUED
    handle: object = None


def _counts(shares: list[float], total: int) -> list[int]:
    raw = np.asarray(shares, np.float64) / sum(shares) * total
    counts = np.floor(raw).astype(int)
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:total - counts.sum()]] += 1
    return counts.tolist()


def _draw(rng, entry: dict, candidates: np.ndarray) -> dict:
    """The parameters of one request of a mix entry: a source (and a
    target for reach) drawn uniformly from ``candidates``."""
    params = {k: v for k, v in entry.items() if k not in ("kind", "share")}
    params["source"] = int(candidates[rng.integers(candidates.size)])
    if entry["kind"] == "reach":
        params["target"] = int(candidates[rng.integers(candidates.size)])
    return params


def make_schedule(traffic: dict, seconds: float, rng,
                  candidates: np.ndarray) -> list[Arrival]:
    """Arrivals of the lead-in and the window, sorted by due time."""
    rate = float(traffic["rate_qps"])
    lead = float(traffic.get("lead_in_s", 0.0))
    mix = traffic["mix"]
    out = []
    for lo, hi, in_window in ((-lead, 0.0, False), (0.0, seconds, True)):
        total = int(round(rate * (hi - lo)))
        if total == 0:
            continue
        kinds = np.repeat(np.arange(len(mix)),
                          _counts([e["share"] for e in mix], total))
        rng.shuffle(kinds)
        times = np.sort(rng.uniform(lo, hi, total))
        for t, i in zip(times, kinds):
            out.append(Arrival(due=float(t), kind=mix[i]["kind"],
                               params=_draw(rng, mix[i], candidates),
                               in_window=in_window))
    return out


@dataclass
class ClientLog:
    """What the client saw: the schedule with its stamps, and the
    host-clock spans of every tick, relative to the window start."""
    arrivals: list[Arrival]
    window_s: float
    ticks: list[tuple[float, float]] = field(default_factory=list)
    gave_up: bool = False

    def window_requests(self) -> list[Arrival]:
        return [a for a in self.arrivals if a.in_window]


class OpenLoopClient:
    """Drive a service with a schedule. ``submit(arrival)`` returns a
    handle, ``status(handle)`` one of QUEUED/RUNNING/DONE/REJECTED,
    ``step()`` runs one tick, ``busy()`` says whether work is in flight.
    ``span(name)`` makes the benchmark's host spans: one around each
    submit, tick and wait, and ``window`` from the first loop turn at or
    after the window's start to the first at or after its end."""

    def __init__(self, submit, status, step, busy, span,
                 clock=time.perf_counter, sleep=time.sleep):
        self.submit, self.status = submit, status
        self.step, self.busy, self.span = step, busy, span
        self.clock, self.sleep = clock, sleep

    def run(self, arrivals: list[Arrival], seconds: float,
            drain_limit_s: float) -> ClientLog:
        """Offer the lead-in, then the window, then drain what is due in
        the window, giving up ``drain_limit_s`` after the window closes.
        The window starts ``lead`` seconds after this call."""
        lead = -min((a.due for a in arrivals), default=0.0)
        t0 = self.clock() + max(lead, 0.0)
        log = ClientLog(arrivals=arrivals, window_s=float(seconds))
        pending = [a for a in arrivals if a.due < seconds]
        outstanding: list[Arrival] = []
        window = self.span("window")
        edge = 0                    # 0 before the window, 1 in it, 2 after
        i = 0
        while True:
            now = self.clock() - t0
            if edge == 0 and now >= 0.0:
                window.__enter__()
                edge = 1
            if edge == 1 and now >= seconds:
                window.__exit__(None, None, None)
                edge = 2
            while i < len(pending) and pending[i].due <= now:
                a = pending[i]
                with self.span("submit"):
                    a.handle = self.submit(a)
                a.submitted = self.clock() - t0
                a.status = self.status(a.handle)
                if a.status != REJECTED:
                    outstanding.append(a)
                i += 1
            if i == len(pending) and not outstanding:
                break
            if now > seconds + drain_limit_s:
                log.gave_up = True
                break
            if outstanding and self.busy():
                start = self.clock() - t0
                with self.span("tick"):
                    self.step()
                end = self.clock() - t0
                log.ticks.append((start, end))
                still = []
                for a in outstanding:
                    a.status = self.status(a.handle)
                    if a.status in (RUNNING, DONE) and a.running is None:
                        a.running = end
                    if a.status == DONE:
                        a.done = end
                    elif a.status != REJECTED:
                        still.append(a)
                outstanding = still
            elif i < len(pending):
                with self.span("wait"):
                    self.sleep(max(0.0, pending[i].due - (self.clock() - t0)))
            else:
                # work outstanding but the service says it is idle
                log.gave_up = True
                break
        if edge == 1:
            window.__exit__(None, None, None)
        return log
