"""Online detection→recovery control plane.

`StreamingDetector` (the incremental F1 detector consuming span-batched
telemetry) + `ControlPlane` (the policy engine mapping alarms to urgent
checkpoints, predictive drains, and alarm-informed retry placement inside
the event-driven `ClusterSim`).
"""
from refsim.control.policy import (ControlConfig, ControlPlane, ControlStats,
                                  DrainAction, UrgentSave)
from refsim.control.streaming import StreamingDetector, robust_peer_z_block

__all__ = [
    "ControlConfig", "ControlPlane", "ControlStats", "DrainAction",
    "UrgentSave", "StreamingDetector", "robust_peer_z_block",
]
