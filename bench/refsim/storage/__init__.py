"""Cluster-scale storage fabric: N NFS clients sharing one server.

``StorageFabric`` derives the paper's scale-emergent F2 bottleneck
(near-linear aggregate bandwidth at 2-4 nodes, collapse to 21.5% read /
16.0% write utilization at 60-node scale) from finite server service
capacity, fanin-dependent service inflation, and transport backlog.  The
per-client checkpoint view (`refsim.checkpoint.storage`), the campaign
simulation (`refsim.core.cluster`), and the scenario engine
(`refsim.ops`) all consume it.
"""
from refsim.storage.fabric import (LINK_BW_BYTES, STD_READ_SLOTS, STD_RSIZE,
                                  STD_WRITE_SLOTS, STD_WSIZE, FabricConfig,
                                  FabricTransferResult, StorageFabric)

__all__ = [
    "FabricConfig", "StorageFabric", "FabricTransferResult",
    "LINK_BW_BYTES", "STD_WRITE_SLOTS", "STD_READ_SLOTS",
    "STD_WSIZE", "STD_RSIZE",
]
