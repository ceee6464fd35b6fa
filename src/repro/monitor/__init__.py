"""repro.monitor — the pluggable telemetry layer (DESIGN.md §5).

Every snapshot producer is a :class:`MetricSource`; the
:class:`TelemetryBus` polls them, caches, streams, and computes deltas;
``watch()`` renders live.  Jobs push via :func:`publish_step_utilization`.
"""
from repro.monitor.bus import (SPAN_NAMES, SourceStats, TelemetryBus,
                               publish_step_utilization)
from repro.monitor.source import (ArchiveSource, LocalHostSource,
                                  MetricSource, MultiClusterSource,
                                  RegistrySource, SimSource, SourceRegistry,
                                  build_source, default_registry,
                                  merge_snapshots)
from repro.monitor.watch import WatchStats, frame_header, watch

__all__ = [
    "ArchiveSource", "LocalHostSource", "MetricSource", "MultiClusterSource",
    "RegistrySource", "SPAN_NAMES", "SimSource", "SourceRegistry",
    "SourceStats", "TelemetryBus", "WatchStats", "build_source",
    "default_registry", "frame_header", "merge_snapshots",
    "publish_step_utilization", "watch",
]
