"""How a cell is driven: one module per ``driver`` named in a workload
file, each defining ``Driver(cell, seed, program)``.

``program()`` gathers the program's public entries the drivers call. A
test hands a driver a namespace with a broken entry in their place, to
see the check fail.
"""
from __future__ import annotations

import importlib
from types import SimpleNamespace


def program() -> SimpleNamespace:
    """The program's public entries: the lane engine, the analytics
    service, and the types they take."""
    from repro.analytics.api import (AnalyticsRequest, BFSQuery, KHopQuery,
                                     ReachQuery)
    from repro.analytics.engine import LaneEngine
    from repro.core.csr import CSRGraph
    from repro.serving.service import AnalyticsService, ServiceConfig
    return SimpleNamespace(
        CSRGraph=CSRGraph, LaneEngine=LaneEngine,
        AnalyticsService=AnalyticsService, ServiceConfig=ServiceConfig,
        AnalyticsRequest=AnalyticsRequest, BFSQuery=BFSQuery,
        KHopQuery=KHopQuery, ReachQuery=ReachQuery)


def load(name: str):
    """The ``Driver`` class of ``bench/drivers/<name>.py``."""
    return importlib.import_module(f"bench.drivers.{name}").Driver
