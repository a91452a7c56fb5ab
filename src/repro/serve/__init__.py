"""Serving engines: continuous batching over fixed slot pools.

``fit_engine`` serves the paper's workload — matricized LSE curve fits —
in one synchronous process; ``fleet`` replicates it behind a
fault-tolerant dispatcher (retry/hedging, moment-journal replay, graceful
degradation).
"""
from repro.serve.fit_engine import (FitServeEngine, FitServeConfig,
                                    FitRequest)
from repro.serve.fleet import (FitFleet, FleetConfig, FleetRequest,
                               FleetWorker)

__all__ = ["FitServeEngine", "FitServeConfig", "FitRequest",
           "FitFleet", "FleetConfig", "FleetRequest", "FleetWorker"]
