"""Fleet serving subsystem: batched multi-stream Moby.

S concurrent vehicle streams advance through one device-resident, jitted
step per frame (vmap over streams, lax.cond frame treatment), contending
for a shared cell uplink and a batching cloud detector. See
fleet.engine.FleetEngine.
"""
from repro.fleet.cloud import CloudBatcher, CloudBatcherConfig
from repro.fleet.engine import FleetEngine
from repro.fleet.step import (FleetState, FrameInputs, init_fleet_state,
                              make_fleet_step)

__all__ = [
    "CloudBatcher", "CloudBatcherConfig", "FleetEngine",
    "FleetState", "FrameInputs", "init_fleet_state", "make_fleet_step",
]
