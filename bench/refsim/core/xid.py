"""XID error taxonomy and resolution actions (paper Table 3).

XID codes are the paper's failure-classification language (NVIDIA GPU
codes); the taxonomy transfers unchanged to any accelerator fleet — we keep
the codes verbatim so the recovery-policy analysis reads identically
(DESIGN.md §2).
"""
from __future__ import annotations

from dataclasses import dataclass
from enum import Enum


class Resolution(Enum):
    RESTART_APP = "RESTART_APP"        # process/session restart sufficient
    RESET_GPU = "RESET_GPU"            # device reset required
    RESTART_BM = "RESTART_BM"          # node (bare-metal) reboot required
    CONTACT_SUPPORT = "CONTACT_SUPPORT"  # hardware replacement path


@dataclass(frozen=True)
class XidInfo:
    code: int
    description: str
    resolution: Resolution
    action: str
    hardware: bool                     # True -> node isolation + migration


# paper Table 3 (+ §4.3.5 CONTACT_SUPPORT branch for XID 79)
XID_TABLE = {
    79: XidInfo(79, "GPU fell off the bus", Resolution.RESTART_BM,
                "Node reboot", True),
    119: XidInfo(119, "GSP RPC timeout", Resolution.RESET_GPU,
                 "GPU reset", True),
    145: XidInfo(145, "NVLink RLW error", Resolution.RESET_GPU,
                 "GPU reset", True),
    149: XidInfo(149, "NVLink NETIR error", Resolution.RESET_GPU,
                 "GPU reset", True),
    31: XidInfo(31, "GPU memory page fault", Resolution.RESTART_APP,
                "Session restart", False),
    43: XidInfo(43, "GPU processing halted", Resolution.RESTART_APP,
                "Session restart", False),
    94: XidInfo(94, "Contained ECC error", Resolution.RESTART_APP,
                "Auto-corrected", False),
}

# Minder-category mapping used by the failure-taxonomy benchmark (Table 2)
MINDER_CATEGORY = {
    145: "NVLink errors", 149: "NVLink errors",
    94: "ECC errors",
    79: "GPU card dropout",
    119: "GPU execution errors",
    31: "GPU execution errors", 43: "GPU execution errors",
}


def classify(code: int) -> XidInfo:
    return XID_TABLE[code]


def requires_isolation(code: int) -> bool:
    """Hardware-action XIDs (79/119/145/149) trigger node isolation +
    session migration; application-level XIDs retry in place (paper §2.3)."""
    return XID_TABLE[code].hardware
