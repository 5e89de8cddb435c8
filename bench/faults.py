"""The program with its timed path broken, to show that the check fails.

``program_with(variant)`` returns the program's entries (as
``bench.drivers.program()`` does) with the lane engine and the service
replaced by subclasses that break their answers where they are made:

* ``control``: each answer read one level too early. A sweep leaves out
  the last level of every key (the tempting cut of a dense engine, whose
  tail levels cost a full pass for few vertices), and so does a BFS
  answer; a k-hop answer holds the band of k - 1, and a reach answer
  reads a target found at the last level as unreachable. This breaks
  the configurations' guarantee of exact hop distances.
* ``stale``: a step that returns its state unchanged. Sweeps return the
  state they start from (only the keys reached); the service's ticks do
  nothing once the traffic has started, so no request is answered.
* ``half``: half of the batch left out. A sweep traverses only the first
  half of its keys; the service drops every second request.
* ``altered``: one answer altered where it is produced: one depth of the
  first key of each sweep and of each BFS answer, and the count of each
  k-hop answer.
* ``rejected``: the service refuses every second request at its front
  door, as a smaller queue bound or load shedding would (served cells
  only).

``bench/control.py`` runs a variant on the chip; the tests run each at a
small size on the CPU.
"""
from __future__ import annotations

import dataclasses

import numpy as np

VARIANTS = ("control", "stale", "half", "altered", "rejected")
# variants that have no meaning for a driver's entry point
_NOT_FOR = {"sweep": ("rejected",)}


def variants_for(driver: str) -> tuple[str, ...]:
    """The variants a cell driven by ``driver`` can have."""
    return tuple(v for v in VARIANTS if v not in _NOT_FOR.get(driver, ()))


def _engine(base, variant: str):
    import jax.numpy as jnp

    class Engine(base):
        def sweep(self, roots, derive_parents: bool = False):
            roots = np.asarray(roots, np.int32).reshape(-1)
            lanes = jnp.arange(roots.size)
            if variant == "half":
                keep = roots[:roots.size // 2]
                res = super().sweep(keep, derive_parents)
                pad = ((0, 0), (0, roots.size - keep.size))
                return res._replace(
                    depth=jnp.pad(res.depth, pad, constant_values=-1),
                    parent=jnp.pad(res.parent, pad, constant_values=-1))
            res = super().sweep(roots, derive_parents)
            depth, parent = res.depth, res.parent
            if variant == "stale":
                start = jnp.full(depth.shape, -1, depth.dtype)
                depth = start.at[roots, lanes].set(0)
                parent = start.at[roots, lanes].set(jnp.asarray(roots))
            elif variant == "control":
                last = (depth == depth.max(axis=0, keepdims=True)) & (depth > 0)
                depth = jnp.where(last, -1, depth)
                parent = jnp.where(last, -1, parent)
            elif variant == "altered":
                v = int(jnp.argmax(depth[:, 0]))
                depth = depth.at[v, 0].add(1)
            return res._replace(depth=depth, parent=parent)

    return Engine


@dataclasses.dataclass
class _Dropped:
    """The record of a request the broken service never admitted."""
    request: object
    status: str = "QUEUED"
    answer: object = None


def _service(base, variant: str):
    class Service(base):
        _armed = False
        _seen = 0
        _dropped: dict

        def submit(self, request):
            traffic = not str(request.id).startswith("warm")
            self._armed = self._armed or traffic
            if traffic and variant in ("half", "rejected"):
                self._seen += 1
                if self._seen % 2 == 0:
                    self.__dict__.setdefault("_dropped", {})
                    self._dropped[request.id] = (
                        _Dropped(request) if variant == "half" else
                        _Dropped(request, "REJECTED"))
                    return self._dropped[request.id]
            return super().submit(request)

        def step(self):
            if variant == "stale" and self._armed:
                return self.busy()
            return super().step()

        def record(self, request_id):
            dropped = self.__dict__.get("_dropped", {})
            if request_id in dropped:
                return dropped[request_id]
            rec = super().record(request_id)
            if rec.answer is None or variant not in ("altered", "control"):
                return rec
            res = rec.answer.result
            if hasattr(res, "reached"):
                depth = np.asarray(res.depth).copy()
                if variant == "altered":
                    depth[int(np.argmax(depth[:, 0])), 0] += 1
                else:
                    last = depth.max(axis=0, keepdims=True)
                    depth[(depth == last) & (depth > 0)] = -1
                res = dataclasses.replace(
                    res, depth=depth,
                    reached=(depth >= 0).sum(axis=0).astype(np.int64),
                    num_layers=np.asarray(res.num_layers)
                    - (variant == "control"))
            elif hasattr(res, "counts"):
                if variant == "altered":
                    res = dataclasses.replace(res, counts=res.counts + 1)
                else:
                    depth = np.asarray(res.depth).copy()
                    depth[depth >= max(res.k, 1)] = -1
                    band = (depth >= 0) & (depth <= res.k)
                    res = dataclasses.replace(
                        res, depth=depth,
                        counts=band.sum(axis=0).astype(np.int64),
                        words=band.astype(np.asarray(res.words).dtype))
            elif variant == "control":
                hops = np.asarray(res.hops)
                res = dataclasses.replace(res, hops=np.where(hops > 0, -1,
                                                              hops))
            answer = dataclasses.replace(rec.answer, result=res)
            return dataclasses.replace(rec, answer=answer)

    return Service


def program_with(variant: str):
    """The program's entries with ``variant`` planted underneath."""
    from bench.drivers import program
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; one of {VARIANTS}")
    p = program()
    p.LaneEngine = _engine(p.LaneEngine, variant)
    p.AnalyticsService = _service(p.AnalyticsService, variant)
    return p
