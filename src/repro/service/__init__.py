"""repro.service -- multi-tenant streaming estimation service.

Sliding-window SJPC sketches behind a registry of named streams, batched
single-dispatch ingest, and a snapshot query engine with analytical error
bars.  See DESIGN.md §10 for the architecture and invariants.
"""
from .client import MonitorServiceClient
from .ingest import (IngestPipeline, ingest_key, ingest_key_grid,
                     multi_round_update)
from .planner import PlannerConfig, QueryPlanner
from .query import (ContinuousQuery, QueryEngine, QueryResult, Snapshot,
                    reference_snapshot)
from .registry import HashGroup, StreamEntry, StreamRegistry
from .service import EstimationService, ServiceConfig
from .window import WindowedSketch

__all__ = [
    "ContinuousQuery", "EstimationService", "HashGroup", "IngestPipeline",
    "MonitorServiceClient", "PlannerConfig", "QueryEngine", "QueryPlanner",
    "QueryResult", "ServiceConfig", "Snapshot", "StreamEntry",
    "StreamRegistry", "WindowedSketch", "ingest_key", "ingest_key_grid",
    "multi_round_update", "reference_snapshot",
]
