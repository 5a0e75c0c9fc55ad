"""Per-client NFS RPC-slot view of the shared storage fabric — paper F2.

The paper's key finding: checkpoint I/O uses only 1.4-10.4% of the 200 Gbps
RoCE link because the bottleneck is the 128-slot NFS RPC layer, not the
network.  We model the client RPC lifecycle exactly as the paper decomposes
it: (1) slot wait (queueing for one of ``n_slots`` concurrent RPCs) and
(2) network+server processing (service time per RPC).  A discrete-event
simulation over request arrivals yields per-request latency decomposition,
achieved bandwidth, and therefore the bandwidth paradox — *derived*, not
assumed.

Since the cluster-scale refactor this module is a thin per-client window
onto `refsim.storage.StorageFabric`: the per-RPC service times are no
longer free constants but the fabric's *effective* service at the
campaign's gang fanin — WRITE at the ~39-node effective writeback fanin
and READ at the 60-node restart-load fanin reproduce the paper's Table 13
values (126 ms / 27.3 ms) to within 2%.  Passing explicit
``write_service_s`` / ``read_service_s`` (e.g. degraded-storage
scenarios) bypasses the derivation.
"""
from __future__ import annotations

import dataclasses
import heapq
from dataclasses import dataclass
from typing import List, Literal, Optional

import numpy as np

from refsim.storage.fabric import (LINK_BW_BYTES, STD_READ_SLOTS,
                                  STD_WRITE_SLOTS, StorageFabric)

__all__ = ["LINK_BW_BYTES", "NFSConfig", "NFSClientSim", "RPCResult",
           "TransferResult"]


@dataclass(frozen=True)
class NFSConfig:
    n_slots: int = 128                 # client RPC slot table (paper)
    # None -> derived from the storage fabric at the fanins below
    # (fabric-effective Table 13: WRITE ~126 ms, READ ~27.3 ms)
    write_service_s: Optional[float] = None
    read_service_s: Optional[float] = None
    wsize: int = 1 << 20               # 1 MiB write RPCs
    rsize: int = 256 << 10             # 256 KiB effective read RPCs
    service_jitter: float = 0.15       # lognormal-ish spread
    n_connections: int = 1             # nconnect mounts (slots multiply)
    write_fanin: int = 39              # effective concurrent writers: saves
                                       #   destagger in the writeback window
    read_fanin: int = 60               # restart loads: the whole gang


@dataclass
class RPCResult:
    op: str
    arrival_s: float
    slot_wait_s: float
    service_s: float

    @property
    def latency_s(self) -> float:
        return self.slot_wait_s + self.service_s


@dataclass
class TransferResult:
    op: str
    total_bytes: int
    n_rpcs: int
    duration_s: float
    mean_slot_wait_s: float
    mean_service_s: float
    results: Optional[List[RPCResult]] = None

    @property
    def mean_latency_s(self) -> float:
        return self.mean_slot_wait_s + self.mean_service_s

    @property
    def slot_wait_fraction(self) -> float:
        m = self.mean_latency_s
        return self.mean_slot_wait_s / m if m > 0 else 0.0

    @property
    def bandwidth_bytes_s(self) -> float:
        return self.total_bytes / self.duration_s if self.duration_s > 0 else 0.0

    @property
    def bandwidth_utilization(self) -> float:
        return self.bandwidth_bytes_s / LINK_BW_BYTES

    @property
    def request_rate_s(self) -> float:
        return self.n_rpcs / self.duration_s if self.duration_s > 0 else 0.0


class NFSClientSim:
    """Discrete-event simulation of one node's NFS client RPC slot table.

    Service times come from the shared ``StorageFabric`` (contention at the
    configured fanin baked in) unless the config pins them explicitly.
    """

    def __init__(self, config: Optional[NFSConfig] = None, seed: int = 0,
                 fabric: Optional[StorageFabric] = None):
        self.fabric = fabric or StorageFabric()
        self.config = self._resolve_config(config or NFSConfig())
        self.rng = np.random.default_rng(seed)

    def _resolve_config(self, config: NFSConfig) -> NFSConfig:
        """Fill None service times from the fabric.

        Derivation uses the fleet-standard slot tables, not this client's
        local override: the fanin inflation reflects what the REST of the
        cluster keeps in flight at the server."""
        w, r = config.write_service_s, config.read_service_s
        if w is None:
            w = self.fabric.service_time_s("write", config.write_fanin,
                                           STD_WRITE_SLOTS, config.wsize)
        if r is None:
            r = self.fabric.service_time_s("read", config.read_fanin,
                                           STD_READ_SLOTS, config.rsize)
        return dataclasses.replace(config, write_service_s=w,
                                   read_service_s=r)

    def _service_time(self, op: str, cfg: NFSConfig) -> float:
        base = cfg.write_service_s if op == "write" else cfg.read_service_s
        if cfg.service_jitter <= 0:
            return base
        return float(base * self.rng.lognormal(
            mean=0.0, sigma=cfg.service_jitter))

    def transfer(self, op: Literal["write", "read"], total_bytes: int,
                 arrival_rate_rpcs_s: Optional[float] = None,
                 burst: int = 1, keep_results: bool = False,
                 config: Optional[NFSConfig] = None) -> TransferResult:
        """Simulate moving ``total_bytes`` through the slot table.

        ``arrival_rate_rpcs_s``: request generation rate.  Checkpoint saves
        dump everything at once (writeback flush -> effectively infinite
        arrival rate -> pure slot-queueing, the paper's 92% slot-wait case);
        loads are paced by readahead (finite rate).

        ``config``: per-call override (e.g. the load path's nconnect=2
        mount) — the shared ``self.config`` is never mutated, so a load is
        safe against a concurrent save from the manager's flush thread.
        """
        cfg = self._resolve_config(config) if config is not None \
            else self.config
        rpc_size = cfg.wsize if op == "write" else cfg.rsize
        n = max(int(np.ceil(total_bytes / rpc_size)), 1)

        if arrival_rate_rpcs_s is None:
            arrivals = np.zeros(n)                      # burst: all at t=0
        else:
            arrivals = np.arange(n, dtype=np.float64) / arrival_rate_rpcs_s
            if burst > 1:
                # readahead issues window-sized burts: quantize arrivals so
                # ``burst`` requests land together (slot-queue contention)
                arrivals = (np.floor(np.arange(n) / burst) * burst
                            / arrival_rate_rpcs_s)

        # min-heap of slot free times (nconnect multiplies the slot table)
        slots = [0.0] * (cfg.n_slots * cfg.n_connections)
        heapq.heapify(slots)
        waits = np.empty(n)
        services = np.empty(n)
        end = 0.0
        results: List[RPCResult] = []
        for i in range(n):
            t_arr = arrivals[i]
            t_slot = heapq.heappop(slots)
            start = max(t_arr, t_slot)
            waits[i] = start - t_arr
            svc = self._service_time(op, cfg)
            services[i] = svc
            fin = start + svc
            heapq.heappush(slots, fin)
            end = max(end, fin)
            if keep_results:
                results.append(RPCResult(op, t_arr, waits[i], svc))

        return TransferResult(
            op=op, total_bytes=total_bytes, n_rpcs=n,
            duration_s=float(end),
            mean_slot_wait_s=float(waits.mean()),
            mean_service_s=float(services.mean()),
            results=results if keep_results else None)

    # -- paper-scenario helpers ---------------------------------------------

    def checkpoint_save(self, bytes_per_node: int = 20 << 30) -> TransferResult:
        """Burst write (writeback flush of the staging buffer)."""
        return self.transfer("write", bytes_per_node)

    def checkpoint_load(self, bytes_per_node: int = 200 << 30,
                        readahead_rpcs_s: float = 8800.0) -> TransferResult:
        """Sustained read at the paper's observed 8-9k req/s/node pace.

        Loads run over nconnect=2 mounts (two slot tables) — required to
        sustain the observed request rate; the override is a per-call
        config, never a mutation of the shared one."""
        cfg = dataclasses.replace(self.config, n_connections=2)
        return self.transfer("read", bytes_per_node,
                             arrival_rate_rpcs_s=readahead_rpcs_s,
                             burst=512, config=cfg)
