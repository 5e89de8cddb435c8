"""An ``AnalyticsService`` under open-loop traffic of one-source
requests: Graph 500 BFS searches (``bfs``), and the k-hop and reach
requests (``khop``, ``reach``) that a mix may also ask for.

Set-up makes the configuration's graph on the device (see
``bench.lib.graphgen``), builds the service, calls ``warmup()``, and
serves one full epoch of cheap requests of the mix's kinds (from vertices
without an edge) plus one more, so that the pool's step, read-out and
epoch recycle have all run once before the traffic starts. Then the
client (``bench.lib.openloop``) offers the lead-in and the window at the
rate of the traffic file, with sources (and targets) drawn from the run's
seed among the Graph 500 search keys, and drains what is due in the
window.

After the drain every request due in the window is judged: one refused
counts as ``rejected``, one admitted and never answered as
``unanswered``, and every answer is compared with the plain reference
(``bench.lib.refs.multi_source_depths``, 64 sources at a time): a BFS
answer's depths, reached count and layer count, a k-hop answer's members,
count and packed bits, a reach answer's hop count.
"""
from __future__ import annotations

import resource
import time

import numpy as np

from bench.lib import graphgen, openloop, refs
from bench.lib.stats import percentile

_STATUS = {"queued": openloop.QUEUED, "running": openloop.RUNNING,
           "done": openloop.DONE, "rejected": openloop.REJECTED}


class Driver:
    def __init__(self, cell, seed: int, program):
        self.cell, self.seed, self.program = cell, int(seed), program
        self.cfg, self.traffic = cell.config, cell.workload
        self.log: openloop.ClientLog | None = None

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        import jax
        cfg, p = self.cfg, self.program
        t0 = time.perf_counter()
        row_ptr, col_idx, src_idx, _ = jax.block_until_ready(
            graphgen.graph_for(cfg, self.seed))
        self.setup_parts = {"graph_s": time.perf_counter() - t0}
        self.row_ptr = np.asarray(row_ptr)
        self.col_idx = np.asarray(col_idx)
        self.candidates = graphgen.search_keys(self.row_ptr, self.col_idx)
        g = p.CSRGraph(row_ptr=row_ptr, col_idx=col_idx, src_idx=src_idx)
        svc = cfg["service"]
        self.service = p.AnalyticsService(g, p.ServiceConfig(
            lanes=svc["lanes"], slots=svc["slots"],
            streaming=svc["streaming"], max_pending=svc["max_pending"]))
        self.service.warmup()
        # cheap requests: each source's search ends after its first layer
        quiet = np.argsort(np.diff(self.row_ptr), kind="stable")
        kinds = [e["kind"] for e in self.traffic["mix"]]
        for i in range(svc["slots"] + 1):
            a = openloop.Arrival(
                due=0.0, kind=kinds[i % len(kinds)], in_window=False,
                params={"k": 0, "source": int(quiet[i % quiet.size]),
                        "target": int(quiet[i % quiet.size])})
            self.service.submit(p.AnalyticsRequest(
                query=self._query(a), id=f"warm{i}"))
        while self.service.busy():
            self.service.step()
        self.setup_parts["warmup_s"] = (time.perf_counter() - t0
                                        - self.setup_parts["graph_s"])
        self.schedule = openloop.make_schedule(
            self.traffic, self.seconds, graphgen.host_rng(self.seed, 1),
            self.candidates)

    # -- window --------------------------------------------------------------

    def _query(self, a: openloop.Arrival):
        p = self.program
        src = (a.params["source"],)
        if a.kind == "bfs":
            return p.BFSQuery(sources=src)
        if a.kind == "khop":
            return p.KHopQuery(sources=src, k=int(a.params["k"]))
        if a.kind == "reach":
            return p.ReachQuery(sources=src, targets=(a.params["target"],))
        raise ValueError(f"traffic kind {a.kind!r} has no request type")

    def _submit(self, a: openloop.Arrival):
        rec = self.service.submit(
            self.program.AnalyticsRequest(query=self._query(a)))
        return rec.request.id

    def _status(self, rid) -> str:
        return _STATUS[self.service.record(rid).status.lower()]

    def window(self, seconds: float, span) -> None:
        client = openloop.OpenLoopClient(
            submit=self._submit, status=self._status,
            step=self.service.step, busy=self.service.busy, span=span)
        self.log = client.run(self.schedule, seconds,
                              float(self.traffic["drain_limit_s"]))

    def free(self) -> None:
        pass

    # -- after the window ----------------------------------------------------

    def _answered(self):
        """(arrival, answer) of every request due in the window that was
        answered, and the count of those that were not."""
        got, missing = [], 0
        for a in self.log.window_requests():
            if a.status == openloop.DONE and a.done is not None:
                got.append((a, self.service.record(a.handle).answer))
            else:
                missing += 1
        return got, missing

    def check(self) -> dict:
        got, missing = self._answered()
        wrong = self.wrong_answers(got)
        limits = self.traffic["limits"]
        rejected = sum(1 for a in self.log.window_requests()
                       if a.status == openloop.REJECTED)
        return {
            "attempted": len(self.log.window_requests()),
            "failed": missing + wrong,
            "numbers": {
                "rejected": (rejected, limits["rejected"]),
                "unanswered": (missing - rejected, limits["unanswered"]),
                "wrong_answers": (wrong, limits["wrong_answers"]),
            },
        }

    def reference_depths(self, arrivals) -> list[np.ndarray]:
        """Reference depth columns for ``arrivals``, 64 sources at a time.
        A batch of k-hop requests only goes as deep as its largest k."""
        def need(a):
            return a.params["k"] if a.kind == "khop" else None

        order = sorted(range(len(arrivals)),
                       key=lambda i: (need(arrivals[i]) is None,
                                      need(arrivals[i]) or 0))
        out: list[np.ndarray | None] = [None] * len(arrivals)
        for lo in range(0, len(order), 64):
            idx = order[lo:lo + 64]
            needs = [need(arrivals[i]) for i in idx]
            depth = refs.multi_source_depths(
                self.row_ptr, self.col_idx,
                [arrivals[i].params["source"] for i in idx],
                max_depth=None if None in needs else max(needs))
            for j, i in enumerate(idx):
                out[i] = depth[:, j]
        return out

    def wrong_answers(self, got) -> int:
        """Answers that differ from the reference."""
        want = self.reference_depths([a for a, _ in got])
        wrong = 0
        for (a, answer), ref in zip(got, want):
            res = answer.result
            if a.kind == "bfs":
                col = np.asarray(res.depth)[:, 0]
                ok = (np.array_equal(col, ref)
                      and int(np.asarray(res.reached)[0])
                      == int((ref >= 0).sum())
                      and int(np.asarray(res.num_layers)[0])
                      == int(ref.max()) + 1)
            elif a.kind == "khop":
                k = int(a.params["k"])
                band = refs.khop_band(ref, k)
                col = np.asarray(res.depth)[:, 0]
                bits = (np.asarray(res.words)[:, 0] & 1).astype(bool)
                ok = (res.k == k
                      and int(np.asarray(res.counts)[0]) == int(band.sum())
                      and np.array_equal(refs.khop_band(col, k), band)
                      and np.array_equal(bits, band))
            else:
                hops = refs.reach_hops(ref, [a.params["target"]])
                ok = np.array_equal(np.asarray(res.hops).reshape(-1), hops)
            wrong += not ok
        return wrong

    # -- metrics -------------------------------------------------------------

    def latencies_ms(self) -> list[float]:
        """Due-to-answer latency of every request due in the window; one
        refused or never answered counts as answered when the client gave
        up, later than any answered one."""
        give_up = self.log.window_s + float(self.traffic["drain_limit_s"])
        return [1e3 * ((a.done if a.status == openloop.DONE
                        and a.done is not None else give_up) - a.due)
                for a in self.log.window_requests()]

    def end_to_end(self) -> dict:
        lat = self.latencies_ms()
        return {"latency_p95_ms": percentile(lat, 95),
                "latency_p50_ms": percentile(lat, 50)}

    def facts(self) -> dict:
        reqs = self.log.window_requests()
        ticks = [(s, e) for s, e in self.log.ticks
                 if 0.0 <= s < self.log.window_s]
        return {
            "client_lag_ms": [1e3 * (a.submitted - a.due) for a in reqs
                              if a.submitted is not None],
            "queue_wait_ms": [1e3 * ((a.running if a.running is not None
                                      else self.log.window_s
                                      + float(self.traffic["drain_limit_s"]))
                                     - a.due) for a in reqs],
            "tick_ms": [1e3 * (e - s) for s, e in ticks],
            "window_s": self.log.window_s,
            # every answered BFS request keeps its tick's read-out alive
            "host_rss_peak_bytes": 1024 * resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss,
        }

    @property
    def seconds(self) -> float:
        return self.cell.seconds

