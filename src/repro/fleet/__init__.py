"""Fleet-scale fingerprint service (paper §III-C at fleet traffic).

- ``store``   — append-only columnar :class:`FingerprintStore` with
  per-(node x benchmark type) time-windowed views and atomic .npz
  durability;
- ``shard``   — :class:`ShardedScorer`, shard_map'd scoring across a
  1-D device mesh reusing the engine's pure score function;
- ``service`` — :class:`FleetScoringService`, micro-batched request
  queue dispatching one sharded call per shape bucket, with NaN/Inf
  and unknown-type quarantine at intake;
- ``drift``   — store-backed per-node / per-aspect EWMA degradation
  analytics (batch ``drift_report`` and incremental ``RollingDrift``)
  consumed by ``runtime.watchdog.PeronaWatchdog``;
- ``ingest``  — :class:`IngestionDaemon`, the long-lived streaming
  front-end: bounded ring staging, deadline/pow2 flush triggers, an
  explicit backpressure ladder and crash-safe shutdown;
- ``faults``  — deterministic seeded fault injection over telemetry
  streams (dropout, stalls, delays, duplicates, reordering, NaN/Inf
  corruption, burst storms) for robustness tests and benchmarks;
- ``modelplane`` — :class:`ModelRegistry` (versioned, crash-safe
  parameter checkpoints) and :class:`ModelPlane` (canary-gated
  zero-downtime promote/rollback on the live service, with the
  drift-triggered retrain loop).
"""

from repro.fleet.drift import (EwmaMean, NodeDrift, RollingDrift,
                               degradation_factors, degrading_nodes,
                               drift_report, ewma_series)
from repro.fleet.faults import (FaultLog, FaultPlan, TelemetryEvent,
                                corrupt_frame, fleet_telemetry,
                                inject_faults)
from repro.fleet.ingest import IngestionDaemon, load_staging, save_staging
from repro.fleet.service import FleetResult, FleetScoringService
from repro.fleet.shard import ScorerCompileError, ShardedScorer
from repro.fleet.store import FingerprintStore, atomic_savez
# last: modelplane leans on repro.obs.regress, which imports
# repro.fleet.drift — already initialized by this point
from repro.fleet.modelplane import ModelPlane, ModelRegistry

__all__ = [
    "FingerprintStore", "ShardedScorer", "ScorerCompileError",
    "FleetScoringService",
    "EwmaMean", "FleetResult", "NodeDrift", "RollingDrift", "drift_report",
    "degradation_factors", "degrading_nodes", "ewma_series",
    "IngestionDaemon", "save_staging", "load_staging",
    "TelemetryEvent", "FaultPlan", "FaultLog", "fleet_telemetry",
    "inject_faults", "corrupt_frame", "atomic_savez",
    "ModelPlane", "ModelRegistry",
]
