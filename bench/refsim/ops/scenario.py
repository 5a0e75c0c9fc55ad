"""Declarative campaign scenarios.

A ``Scenario`` is the single front door for "what if the campaign had
looked different": it composes the failure mix (MTBF + category tilts +
hot-node skew), the auto-retry policy (paper-faithful FIXED, §4.3.5
EXP_BACKOFF / XID_BRANCH / structural-stop), the checkpoint strategy
(observed fixed interval vs Young-Daly optimum), and the storage model
(NFS RPC-slot simulation driving save/load times) into one named,
serializable spec that resolves to a `CampaignConfig`.

Presets cover the paper's own campaign plus the what-if corners the
ROADMAP asks for; ``Scenario.to_dict`` / ``from_dict`` round-trip so sweeps
can ship specs across process boundaries (and users can keep them in JSON).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from refsim.checkpoint.storage import NFSClientSim, NFSConfig
from refsim.checkpoint.youngdaly import MTBF_H_PAPER, t_opt_s
from refsim.control.policy import ControlConfig
from refsim.core.cluster import CampaignConfig
from refsim.core.failures import FAILURE_CATEGORIES
from refsim.core.retry import RetryConfig, RetryPolicy
from refsim.storage.fabric import FabricConfig, StorageFabric


@dataclass
class Scenario:
    """One named operational what-if, resolvable to a `CampaignConfig`."""

    name: str
    description: str = ""

    # -- cluster shape ------------------------------------------------------
    n_nodes: int = 63
    job_nodes: int = 60
    duration_days: float = 73.0

    # -- failure model ------------------------------------------------------
    mtbf_h: float = MTBF_H_PAPER
    hot_fraction: float = 0.05
    hot_weight: float = 0.55
    # category -> multiplicative tilt on the paper's Table 2 mix
    # (nvlink | ecc | dropout | exec | app | unreachable | fail_slow)
    kind_weights: Optional[Dict[str, float]] = None

    # -- retry policy -------------------------------------------------------
    retry_policy: str = "fixed"           # fixed | exp_backoff | xid_branch
    retry_enabled: bool = True
    max_retries: int = 30
    retry_delay_min: float = 10.0
    structural_stop: bool = False         # §4.3.5 improvement 3

    # -- checkpoint strategy ------------------------------------------------
    checkpoint_strategy: str = "fixed"    # fixed | young_daly
    checkpoint_interval_h: float = 2.23   # used when strategy == "fixed"
    checkpoint_delta_s: float = 18.0      # save duration (4K-phase paper value)
    # when set, the save duration is *derived* from the NFS RPC-slot model
    # instead of taken from ``checkpoint_delta_s``
    ckpt_bytes_per_node: Optional[int] = None
    ckpt_wire_ratio: float = 0.5          # ckpt_pack fp32->bf16 wire volume
                                          #   (1.0 models pack="xor")

    # -- storage model ------------------------------------------------------
    storage_slots: int = 128              # NFS client RPC slot table
    storage_degradation: float = 1.0      # service-time / load-time multiplier
    # shared-NFS fabric (paper F2): when True, save duration AND restart
    # loading time are derived from fabric queries at the gang fanin
    # (scale-emergent contention) instead of the per-client constants
    storage_fabric: bool = False
    storage_server_read_gbs: float = 700.0   # aggregate read max (paper)
    storage_server_write_gbs: float = 250.0  # aggregate write max (paper)
    restore_bytes_per_node: int = 200 << 30

    # -- telemetry / F1 -----------------------------------------------------
    telemetry: bool = False               # scrape during the main campaign
    telemetry_days: float = 0.0           # F1 sub-campaign window (0 = no F1)
    # None = the full paper-realistic ~305-metric registry (detector FP
    # behaviour at the true metric count); set lower to trade FP fidelity
    # for memory in wide sweeps
    telemetry_pad_metrics: Optional[int] = None

    # -- detection->recovery control plane ----------------------------------
    # when True the campaign runs the online control loop: the streaming
    # detector consumes span-batched telemetry as it is emitted
    # (stream-and-discard; nothing retained) and maps alarms to recovery
    # actions.  The reactive baseline is simply control_plane=False.
    control_plane: bool = False
    control_urgent_checkpoint: bool = True   # in-gang alarm -> urgent save
    control_drain: bool = False              # confirmed alarm -> drain node
    control_drain_confirm_alarms: int = 3    # same-node alarms that confirm
    control_alarm_memory_h: float = 4.0      # retry placement avoids alarmed
    # log channel (L4): synthetic operational logs analyzed alongside the
    # metric vote — template bursts + cross-node references attribute
    # gang-wide symptoms to a root-cause node, fused into the same alarm
    # stream.  Requires control_plane; off by default (bit-identity).
    log_channel: bool = False
    # blast-radius-aware recovery (correlated fault band): attribute
    # gang-wide alarm bursts to the shared leaf switch, suppress member
    # drains while the switch is indicted, and re-place retries away from
    # the degraded rack.  Requires control_plane; off by default.
    blast_radius_aware: bool = False
    topology_fanout: int = 8              # nodes per leaf switch (the
                                          #   switch_degrade blast radius)
    # streaming-detector pass-1 implementation: only "numpy" here
    detector_backend: str = "numpy"

    # escape hatch: raw CampaignConfig field overrides applied last
    overrides: Dict[str, float] = field(default_factory=dict)

    def __post_init__(self):
        RetryPolicy(self.retry_policy)                  # validate early
        if self.detector_backend != "numpy":
            raise ValueError("the reference has the numpy detector only")
        if self.checkpoint_strategy not in ("fixed", "young_daly"):
            raise ValueError(
                f"unknown checkpoint_strategy {self.checkpoint_strategy!r}")
        unknown = set(self.kind_weights or ()) - FAILURE_CATEGORIES
        if unknown:
            raise ValueError(
                f"unknown kind_weights categories {sorted(unknown)}; "
                f"valid: {sorted(FAILURE_CATEGORIES)}")
        if self.log_channel and not self.control_plane:
            raise ValueError(
                "log_channel requires control_plane=True (the log "
                "analyzer's verdicts fuse into the control loop)")
        if self.blast_radius_aware and not self.control_plane:
            raise ValueError(
                "blast_radius_aware requires control_plane=True (switch "
                "indictment lives in the control loop)")

    # -- resolution ---------------------------------------------------------

    def fabric_config(self) -> FabricConfig:
        return FabricConfig(
            server_read_bw=self.storage_server_read_gbs * 1e9,
            server_write_bw=self.storage_server_write_gbs * 1e9,
            degradation=self.storage_degradation)

    def fabric(self) -> StorageFabric:
        """The shared-NFS server this scenario's clients contend for."""
        return StorageFabric(self.fabric_config())

    def storage_model(self, seed: int = 0) -> NFSClientSim:
        if self.storage_fabric:
            # per-client view of the shared fabric: service times derived
            # at the campaign fanins, degradation included
            return NFSClientSim(NFSConfig(n_slots=self.storage_slots),
                                seed=seed, fabric=self.fabric())
        cfg = NFSConfig(
            n_slots=self.storage_slots,
            write_service_s=0.126 * self.storage_degradation,
            read_service_s=0.0273 * self.storage_degradation)
        return NFSClientSim(cfg, seed=seed)

    def resolve_delta_s(self) -> float:
        """Checkpoint save duration under this scenario's storage model."""
        if self.storage_fabric:
            wire = int((self.ckpt_bytes_per_node or 20 << 30)
                       * self.ckpt_wire_ratio)
            return float(self.fabric().expected_duration_s(
                "write", self.job_nodes, wire,
                slots_per_client=self.storage_slots))
        if self.ckpt_bytes_per_node is not None:
            nfs = self.storage_model()
            return float(nfs.checkpoint_save(self.ckpt_bytes_per_node)
                         .duration_s)
        return self.checkpoint_delta_s * self.storage_degradation

    def resolve_interval_h(self, delta_s: Optional[float] = None) -> float:
        if delta_s is None:
            delta_s = self.resolve_delta_s()
        if self.checkpoint_strategy == "young_daly":
            return t_opt_s(delta_s, self.mtbf_h) / 3600.0
        return self.checkpoint_interval_h

    def retry_config(self) -> RetryConfig:
        return RetryConfig(enabled=self.retry_enabled,
                           max_retries=self.max_retries,
                           delay_min=self.retry_delay_min,
                           policy=RetryPolicy(self.retry_policy),
                           structural_stop=self.structural_stop)

    def control_config(self) -> Optional[ControlConfig]:
        if not self.control_plane:
            return None
        return ControlConfig(
            urgent_checkpoint=self.control_urgent_checkpoint,
            drain=self.control_drain,
            drain_confirm_alarms=self.control_drain_confirm_alarms,
            alarm_memory_h=self.control_alarm_memory_h,
            log_channel=self.log_channel,
            blast_radius_aware=self.blast_radius_aware,
            topology_fanout=self.topology_fanout,
            detector_backend=self.detector_backend)

    def to_campaign_config(self, seed: int = 0) -> CampaignConfig:
        delta_s = self.resolve_delta_s()
        cfg = CampaignConfig(
            n_nodes=self.n_nodes,
            job_nodes=self.job_nodes,
            duration_h=self.duration_days * 24.0,
            mtbf_h=self.mtbf_h,
            retry=self.retry_config(),
            checkpoint_interval_h=self.resolve_interval_h(delta_s),
            checkpoint_save_s=delta_s,
            loading_time_h=(31.0 / 60.0) * self.storage_degradation,
            loading_cold_h=(58.0 / 60.0) * self.storage_degradation,
            hot_fraction=self.hot_fraction,
            hot_weight=self.hot_weight,
            kind_weights=dict(self.kind_weights)
            if self.kind_weights else None,
            topology_fanout=self.topology_fanout,
            telemetry=self.telemetry,
            telemetry_pad_metrics=self.telemetry_pad_metrics,
            seed=seed,
        )
        if self.storage_fabric:
            # hand ClusterSim the fabric itself: save/loading times are
            # re-derived there from gang-fanin queries (identical to the
            # delta_s above), and telemetry picks up the fabric's
            # queue-depth/backlog levels
            cfg = dataclasses.replace(
                cfg,
                storage=self.fabric_config(),
                storage_slots=self.storage_slots,
                ckpt_bytes_per_node=self.ckpt_bytes_per_node or 20 << 30,
                ckpt_wire_ratio=self.ckpt_wire_ratio,
                restore_bytes_per_node=self.restore_bytes_per_node)
        if self.control_plane:
            # online loop: telemetry spans feed the streaming detector and
            # are discarded (day-scale retention is an offline-F1 concern)
            cfg = dataclasses.replace(
                cfg, control=self.control_config(),
                telemetry=True, telemetry_store=False)
        if self.overrides:
            cfg = dataclasses.replace(cfg, **self.overrides)
        return cfg

    # -- serialization ------------------------------------------------------

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    # labels, not semantics: two specs differing only here run the exact
    # same campaign, so the canonical key must treat them as equal
    _LABEL_FIELDS = ("name", "description")

    def canonical_dict(self) -> dict:
        """The semantics of this spec in canonical form.

        Normalization rules (what makes two specs "the same campaign"):

        * ``name``/``description`` are dropped — they label the spec, the
          simulation never reads them (preset-vs-explicit equivalence:
          a preset and a hand-built Scenario with identical fields get
          identical keys);
        * numeric values are canonicalized to ``float`` (``73`` and
          ``73.0`` resolve to the same campaign; bools stay bools);
        * ``kind_weights`` drops identity tilts (``1.0`` multiplies a
          category weight by one) and collapses empty/None to ``None``;
        * ``overrides`` collapses empty to ``{}``; nested dict key order
          never matters (ordering-insensitive by sorted-key dumping).
        """
        def norm(v):
            if isinstance(v, bool) or v is None or isinstance(v, str):
                return v
            if isinstance(v, (int, float)):
                return float(v)
            if isinstance(v, dict):
                return {k: norm(x) for k, x in sorted(v.items())}
            raise TypeError(
                f"unserializable scenario field value {v!r}")
        d = {k: norm(v) for k, v in self.to_dict().items()
             if k not in self._LABEL_FIELDS}
        kw = {k: v for k, v in (d.get("kind_weights") or {}).items()
              if v != 1.0}
        d["kind_weights"] = kw or None
        d["overrides"] = d.get("overrides") or {}
        return d

    def canonical_key(self) -> str:
        """Stable cache key for this spec's *semantics*.

        Equal for any two specs that resolve to the same campaign:
        dict-order changes, ``to_dict``/``from_dict`` round-trips, preset
        vs explicit construction, int-vs-float spelling and identity
        kind-weight tilts all collapse to one key (see
        :meth:`canonical_dict`).  The key is the sha256 of the sorted
        canonical JSON, so it is safe as a bounded-length LRU key and
        across processes.
        """
        payload = json.dumps(self.canonical_dict(), sort_keys=True,
                             separators=(",", ":"))
        return hashlib.sha256(payload.encode()).hexdigest()

    @classmethod
    def from_dict(cls, d: dict) -> "Scenario":
        return cls(**d)

    def replace(self, **kw) -> "Scenario":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# named presets
# ---------------------------------------------------------------------------

PRESETS: Dict[str, Scenario] = {s.name: s for s in [
    Scenario(
        name="paper-faithful",
        description="The paper's 73-day 63-node campaign: Table 2 failure "
                    "mix, 10-min fixed auto-retry, 2.23 h checkpoint "
                    "interval (4K-phase median)."),
    Scenario(
        name="flaky-fabric",
        description="NVLink-dominated failure storm: MTBF halved, NVLink "
                    "share x2.5, hot nodes carry 70% of the hazard.",
        mtbf_h=28.0,
        hot_weight=0.70,
        kind_weights={"nvlink": 2.5}),
    Scenario(
        name="storage-degraded",
        description="Overloaded NFS backend: 4x RPC service times (save/"
                    "load stretch accordingly); Young-Daly re-optimises the "
                    "checkpoint interval for the slower saves.",
        storage_degradation=4.0,
        ckpt_bytes_per_node=20 << 30,
        checkpoint_strategy="young_daly"),
    Scenario(
        name="storage-fabric",
        description="Paper campaign with checkpoint timing DERIVED from "
                    "the shared-NFS fabric at gang fanin (F2: 21.5%/16.0% "
                    "aggregate utilization at 60-node scale, near-linear "
                    "at 2-4 nodes) instead of the observed constants.",
        storage_fabric=True),
    Scenario(
        name="storage-fabric-degraded",
        description="Shared fabric with 4x degraded server service; saves "
                    "and restart loads stretch with gang-fanin contention "
                    "and Young-Daly re-optimises the interval.",
        storage_fabric=True,
        storage_degradation=4.0,
        checkpoint_strategy="young_daly"),
    Scenario(
        name="big-cluster-252",
        description="4x the paper's scale (252 nodes, 240-node gang); fleet "
                    "MTBF shrinks proportionally at constant per-node "
                    "hazard.",
        n_nodes=252,
        job_nodes=240,
        duration_days=30.0,
        mtbf_h=MTBF_H_PAPER * 63.0 / 252.0),
    Scenario(
        name="no-auto-retry",
        description="Paper's counterfactual baseline: every failure is a "
                    "manual operator restart (12.5% chain success, 3.3 h "
                    "median downtime in the paper).",
        retry_enabled=False),
    Scenario(
        name="exp-backoff",
        description="§4.3.5 improvement 1: exponential retry backoff "
                    "(10 -> 20 -> 40 min, capped at 80).",
        retry_policy="exp_backoff"),
    Scenario(
        name="xid-branch",
        description="§4.3.5 improvement 2: XID-classified retry (RESTART_APP"
                    " immediate, RESET_GPU delayed, RESTART_BM pages the "
                    "operator).",
        retry_policy="xid_branch"),
    Scenario(
        name="smart-retry",
        description="§4.3.5 improvement 3: stop retrying when the healthy "
                    "pool cannot satisfy the gang requirement (no more "
                    "30-attempt burn-downs).",
        structural_stop=True),
    Scenario(
        name="young-daly",
        description="Checkpoint at the Young-Daly optimum for the 4K-phase "
                    "delta (44.9 min) instead of the observed 2.23 h.",
        checkpoint_strategy="young_daly"),
    Scenario(
        name="reactive",
        description="Reactive baseline for the control-plane presets: the "
                    "paper campaign where failures are handled only after "
                    "they fire — the F1 detector changes nothing."),
    Scenario(
        name="proactive",
        description="Online detection->recovery: the streaming detector "
                    "consumes telemetry as emitted; in-gang alarms trigger "
                    "urgent checkpoints (fabric-priced at gang fanin) and "
                    "retries avoid recently-alarmed nodes.  Trajectory-"
                    "preserving actions only: goodput gain is the lost-work "
                    "window shrunk by true positives minus save time burned "
                    "by false positives.",
        control_plane=True),
    Scenario(
        name="proactive-aggressive",
        description="Proactive plus predictive drains: alarms confirmed by "
                    "clustering (3 same-node alarms in 30 min) gracefully "
                    "checkpoint, drain, and replace the suspect node before "
                    "the failure lands — the gang dodges the crash entirely "
                    "at the price of a controlled restart (and the "
                    "occasional false-positive drain).",
        control_plane=True,
        control_drain=True),
    Scenario(
        name="infra-faults",
        description="Cluster-infrastructure fault band: network-degradation "
                    "windows (gang-wide collective slowdown), resource-"
                    "exhaustion windows (host pressure, sometimes escalating "
                    "to a crash) and control-plane blind windows (scheduler "
                    "outages that queue decisions), on top of the paper "
                    "mix.  The control plane classifies alarms and throttles "
                    "net windows instead of draining healthy nodes.",
        kind_weights={"net_degrade": 4.0, "resource_exhaust": 4.0,
                      "ctrl_blind": 4.0},
        control_plane=True),
    Scenario(
        name="degraded-network",
        description="Network-degradation-dominated band: latency/loss "
                    "windows inflate collective step time and StorageFabric "
                    "RPC service; the detector sees transport backlog / RPC "
                    "queue signatures and the control plane throttles "
                    "(waits the window out) instead of urgent-saving.",
        kind_weights={"net_degrade": 8.0},
        control_plane=True),
    Scenario(
        name="resource-pressure",
        description="Resource-exhaustion-dominated band: gradual or spike "
                    "host memory/disk pressure slows nodes and sometimes "
                    "escalates to a process crash; confirmed alarms drain "
                    "the pressured node behind a final checkpoint before "
                    "the escalation lands.",
        kind_weights={"resource_exhaust": 8.0},
        control_plane=True,
        control_drain=True),
    Scenario(
        name="ops-blind-spots",
        description="Scheduler-outage band: control-plane blind windows "
                    "queue alarm decisions until visibility returns (the "
                    "outage cost is exactly that latency), layered over "
                    "resource-pressure windows that keep raising alarms.",
        kind_weights={"ctrl_blind": 8.0, "resource_exhaust": 4.0},
        control_plane=True),
    Scenario(
        name="log-fusion-off",
        description="Metric-only twin of log-fusion: the identical infra-"
                    "heavy schedule, control plane and drain policy, with "
                    "the log channel off — the baseline the log channel's "
                    "time-to-detection and false-drain deltas are measured "
                    "against.",
        kind_weights={"net_degrade": 4.0, "resource_exhaust": 4.0,
                      "ctrl_blind": 4.0},
        control_plane=True,
        control_drain=True),
    Scenario(
        name="log-fusion",
        description="Log-channel diagnosis fused with the metric vote "
                    "(L4): a synthetic operational log stream — XID "
                    "bursts, gang-wide NCCL timeouts, NFS/RPC stall spam, "
                    "memory-pressure ramps — is template-mined, burst/"
                    "rarity scored, and root-cause attributed across "
                    "nodes; verdicts merge into the control loop's alarm "
                    "stream.  Compare against log-fusion-off for the "
                    "detection-latency and false-drain deltas.",
        kind_weights={"net_degrade": 4.0, "resource_exhaust": 4.0,
                      "ctrl_blind": 4.0},
        control_plane=True,
        control_drain=True,
        log_channel=True),
    Scenario(
        name="switch-blast",
        description="Correlated fault band, switch-dominated: one leaf "
                    "switch degrades and every node behind it co-degrades "
                    "for the same window (the blast radius the per-node "
                    "fault model cannot express).  Control-free: the "
                    "reactive baseline eats the full gang-wide slowdown.",
        kind_weights={"switch_degrade": 8.0}),
    Scenario(
        name="dns-flaps",
        description="Correlated fault band, flap-dominated: short partial-"
                    "gang connectivity windows where a sampled peer becomes "
                    "unreachable from a small member set (pairwise mask, "
                    "not node-down) — rpc name-resolution noise that looks "
                    "like a sick node but is not.  Control-free baseline.",
        kind_weights={"dns_flap": 8.0}),
    Scenario(
        name="correlated-recovery",
        description="Blast-radius-aware recovery over the full correlated "
                    "band: net-class alarm bursts across one switch's "
                    "members indict the shared switch (Mycroft-style cross-"
                    "node correlation, log lines fused in), member drains "
                    "are suppressed while the switch is indicted, and retry "
                    "placement avoids the degraded rack.  48-node gang in "
                    "the 63-node pool so a full rack can be placed around.",
        job_nodes=48,
        kind_weights={"switch_degrade": 6.0, "dns_flap": 4.0},
        control_plane=True,
        control_drain=True,
        log_channel=True,
        blast_radius_aware=True),
]}


def get_scenario(name: str) -> Scenario:
    """Resolve a preset by name, as a fresh deep copy.

    Presets carry mutable fields (``kind_weights``, ``overrides``); handing
    out the registry instance would let one caller's mutation leak into
    every later ``get_scenario`` of the same name.  The dict round-trip is
    the same canonical form sweeps ship across process boundaries, so the
    copy is also a per-lookup serialization check.
    """
    try:
        return Scenario.from_dict(PRESETS[name].to_dict())
    except KeyError:
        raise KeyError(f"unknown scenario {name!r}; available: "
                       f"{', '.join(sorted(PRESETS))}") from None


def list_scenarios() -> List[str]:
    return sorted(PRESETS)
