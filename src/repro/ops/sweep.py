"""Vectorized campaign sweeps: N seeds x M scenarios -> F1-F4 comparison.

`SweepRunner` fans campaigns out over a `concurrent.futures` executor
(process pool by default — each campaign is an independent, seeded
simulation), computes the paper's four findings per campaign, aggregates
across seeds, and renders a markdown comparison report next to the paper's
published numbers.

The per-campaign worker is a module-level function (`run_campaign`) taking
plain dicts, so specs pickle across process boundaries and results are
deterministic for fixed (scenario, seed) regardless of executor choice.

Monte Carlo mode: ``SweepRunner(scenarios, mc_seeds=256)`` replaces the
one-process-per-seed fan-out with one `BatchedCampaignEngine` pass per
scenario — hundreds of seeds in a single stacked-numpy simulation, with
per-seed findings identical to the pool path (the engine's parity
contract).  At >=8 seeds the report grows distributional columns
(median / IQR / 95% CI of the mean) for the F1-F4 findings and the
proactive-vs-reactive goodput delta, which is the point: headline numbers
from one 73-day trajectory are point estimates; the Monte Carlo layer
reports how wide they actually are.
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing
import os
import time
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Union

import numpy as np

from repro.core.cluster import ClusterSim
from repro.core.failures import CORRELATED_KINDS, INFRA_KINDS
from repro.core.findings import LaneTotals, findings, split_downtimes
from repro.core.retry import chain_stats
from repro.ops.scenario import Scenario, get_scenario

# distributional statistics (median/IQR/CI columns, paired goodput
# deltas, what-if service answers) render from this many seeds up —
# below it, quartiles of a handful of campaigns would be noise dressed
# as rigor.  Shared by the report sections and `repro.serve`.
MIN_DIST_SEEDS = 8

# paper headline values, shown as the reference row of every report
PAPER_REFERENCE = {
    "occupancy": 0.966,            # §3 training occupancy
    "f1_detection_rate": 1.0,      # 10/10 at-XID detection
    "f1_pre_xid_rate": 0.2,        # 2/10 pre-XID
    "f1_fp_per_day": 0.84,
    "f2_load_util": 0.215,         # restart-load share of 700 GB/s read max
    "f2_save_util": 0.160,         # save-burst share of 250 GB/s write max
    "f3_top3_share": 0.50,         # >50% of exclusions on 3 nodes
    "f4_success_rate": 0.333,      # auto-retry chain success
    "f4_gap_median_min": 11.0,     # inter-session gap
    "f4_auto_downtime_h": 1.9,
    "f4_manual_downtime_h": 3.3,
}


# ---------------------------------------------------------------------------
# per-campaign worker (module-level: must pickle for ProcessPoolExecutor)
# ---------------------------------------------------------------------------

def compute_findings(res) -> Dict[str, Optional[float]]:
    """F2-F4 metrics (plus campaign health) from one CampaignResult."""
    chains = res.retry_chains()
    st = chain_stats(chains)
    intervals = res.exclusions.intervals
    autos, mans = split_downtimes(res.downtimes)
    kinds = [f.kind for f in res.failures]
    ctl, drain_excl = None, 0
    if res.control is not None:
        ctl = res.control.summarize(res.failures, res.duration_h)
        drains = res.exclusions.by_reason().get("predictive drain")
        drain_excl = drains["count"] if drains else 0
    return findings(LaneTotals(
        duration_h=res.duration_h, run_h=res.running_h(),
        lost_h=res.lost_hours, ckpt_events=res.checkpoint_events,
        save_s=res.checkpoint_save_s,
        urgent_h=res.control.urgent_save_h if res.control else 0.0,
        degraded_h=res.degraded_hours,
        n_failures=len(res.failures), n_sessions=len(res.sessions),
        excl_counts=res.exclusions.exclusion_counts(),
        n_deliberate=sum(iv.deliberate for iv in intervals),
        n_intervals=len(intervals),
        f4=(st["n_chains"], st["n_attempts"], st["success"]),
        gaps_min=[g for c in chains for g in c.gaps_min()],
        auto_down_h=autos, manual_down_h=mans,
        infra_n=sum(k in INFRA_KINDS for k in kinds),
        corr_n=sum(k in CORRELATED_KINDS for k in kinds),
        switch_ids=[f.switch for f in res.failures
                    if f.kind == "switch_degrade"],
        control=ctl, drain_excl=drain_excl))


def _f1_findings(scenario: Scenario, seed: int) -> Dict[str, float]:
    """F1 precursor metrics from a telemetry-on sub-campaign.

    Full-length telemetry at 30 s x ~300 metrics x n_nodes does not fit in
    memory for 73-day sweeps, so F1 runs on a shorter window
    (``scenario.telemetry_days``); detection and FP rates are per-day
    quantities, so the window length only affects their variance.  The
    full ~305-metric registry is scraped by default (~0.5 GB per 2-day
    campaign, one campaign in flight per pool worker) — set
    ``scenario.telemetry_pad_metrics`` to shrink it for wide sweeps, at
    the cost of FP-rate fidelity.
    """
    from repro.core.precursor import (DetectorConfig, PrecursorDetector,
                                      evaluate)
    # the F1 sub-campaign is an offline scan over a retained store; the
    # online control plane (which discards spans) is disabled for it
    sub = scenario.replace(duration_days=scenario.telemetry_days,
                           telemetry=True, control_plane=False)
    res = ClusterSim(sub.to_campaign_config(seed)).run()
    xid_fails = [f for f in res.failures if f.kind == "xid"]
    # the offline scan is the same pass-1 hot loop the fast path serves:
    # the scenario's backend switch covers it too (alarms identical)
    alarms = PrecursorDetector(
        DetectorConfig(), backend=scenario.detector_backend).scan(res.store)
    ev = evaluate(alarms, xid_fails, res.duration_h)
    # windows with no XID event cannot score detection (None -> skipped in
    # aggregation); the FP rate is meaningful either way
    has_events = ev.n_failures > 0
    return {
        "f1_n_failures": float(ev.n_failures),
        "f1_detection_rate": ev.detection_rate if has_events else None,
        "f1_pre_xid_rate": ev.pre_xid_rate if has_events else None,
        "f1_fp_per_day": ev.fp_per_day,
    }


def _f2_findings(scenario: Scenario) -> Dict[str, float]:
    """F2 storage metrics: aggregate utilization at the gang fanin plus the
    fabric-derived save/restart-read durations (deterministic queries)."""
    fab = scenario.fabric()
    n = scenario.job_nodes
    wslots = scenario.storage_slots
    rslots = 2 * scenario.storage_slots        # nconnect=2 load path
    wire = int((scenario.ckpt_bytes_per_node or 20 << 30)
               * scenario.ckpt_wire_ratio)
    return {
        "f2_load_util": fab.utilization("read", n, rslots),
        "f2_save_util": fab.utilization("write", n, wslots),
        "f2_load_agg_gbs": n * fab.per_client_bandwidth_bytes_s(
            "read", n, rslots) / 1e9,
        "f2_save_agg_gbs": n * fab.per_client_bandwidth_bytes_s(
            "write", n, wslots) / 1e9,
        "f2_save_s": fab.expected_duration_s(
            "write", n, wire, slots_per_client=wslots),
        "f2_restart_read_s": fab.expected_duration_s(
            "read", n, scenario.restore_bytes_per_node,
            slots_per_client=rslots),
    }


def run_campaign(scenario_dict: dict, seed: int) -> dict:
    """Run one (scenario, seed) campaign and return its findings dict."""
    scenario = Scenario.from_dict(scenario_dict)
    t0 = time.perf_counter()
    res = ClusterSim(scenario.to_campaign_config(seed)).run()
    findings = compute_findings(res)
    if scenario.storage_fabric:
        findings.update(_f2_findings(scenario))
    if scenario.telemetry_days > 0:
        findings.update(_f1_findings(scenario, seed))
    findings["wall_s"] = time.perf_counter() - t0
    return {"scenario": scenario.name, "seed": seed, "findings": findings}


# ---------------------------------------------------------------------------
# distribution extraction (shared by the report and the what-if service)
# ---------------------------------------------------------------------------

def findings_distribution(per_seed: Sequence[Dict[str, Optional[float]]]
                          ) -> Dict[str, dict]:
    """metric -> distribution stats over one stack of per-seed findings.

    Each entry carries ``n``, ``mean``, ``median``, ``q25``/``q75`` (the
    IQR) and a normal-approximation 95% CI of the mean (``ci_lo``/
    ``ci_hi``; degenerate at n=1).  ``None`` values (metric not
    applicable for that seed) are skipped; non-numeric metrics are
    dropped.  This is the single extraction both `SweepResult.
    distribution()` (per scenario) and the what-if service (per stacked
    engine pass) run, so a served answer and a report cell computed from
    the same findings are the same numbers.
    """
    keys = sorted({k for f in per_seed for k in f})
    stats: Dict[str, dict] = {}
    for k in keys:
        vals = [f[k] for f in per_seed if f.get(k) is not None]
        if not vals or not all(
                isinstance(v, (int, float)) for v in vals):
            continue
        a = np.asarray(vals, dtype=float)
        mean = float(a.mean())
        if len(a) > 1:
            half = 1.96 * float(a.std(ddof=1)) / np.sqrt(len(a))
        else:
            half = 0.0
        stats[k] = {
            "n": len(a),
            "mean": mean,
            "median": float(np.median(a)),
            "q25": float(np.percentile(a, 25)),
            "q75": float(np.percentile(a, 75)),
            "ci_lo": mean - half,
            "ci_hi": mean + half,
        }
    return stats


# ---------------------------------------------------------------------------
# sweep runner
# ---------------------------------------------------------------------------

@dataclass
class SweepOutcome:
    scenario: str
    seed: int
    findings: Dict[str, Optional[float]]


@dataclass
class SweepResult:
    scenarios: List[Scenario]
    seeds: List[int]
    outcomes: List[SweepOutcome]
    wall_s: float = 0.0

    def aggregate(self) -> Dict[str, Dict[str, float]]:
        """scenario -> metric -> mean over seeds (None values skipped)."""
        out: Dict[str, Dict[str, float]] = {}
        for sc in self.scenarios:
            per = [o.findings for o in self.outcomes if o.scenario == sc.name]
            keys = sorted({k for f in per for k in f})
            agg = {}
            for k in keys:
                vals = [f[k] for f in per if f.get(k) is not None]
                agg[k] = float(np.mean(vals)) if vals else None
            out[sc.name] = agg
        return out

    def distribution(self) -> Dict[str, Dict[str, dict]]:
        """scenario -> metric -> distribution stats over seeds
        (see :func:`findings_distribution` for the per-metric entries)."""
        out: Dict[str, Dict[str, dict]] = {}
        for sc in self.scenarios:
            per = [o.findings for o in self.outcomes if o.scenario == sc.name]
            out[sc.name] = findings_distribution(per)
        return out

    # -- rendering ----------------------------------------------------------

    _COLUMNS = [
        ("occupancy", "occ %", lambda v: f"{v*100:.1f}"),
        ("goodput", "goodput %", lambda v: f"{v*100:.1f}"),
        ("n_failures", "fails", lambda v: f"{v:.0f}"),
        ("f1_detection_rate", "F1 det %", lambda v: f"{v*100:.0f}"),
        ("f1_fp_per_day", "F1 fp/d", lambda v: f"{v:.2f}"),
        ("f2_load_util", "F2 load %", lambda v: f"{v*100:.1f}"),
        ("f2_save_util", "F2 save %", lambda v: f"{v*100:.1f}"),
        ("f3_top3_share", "F3 top3 %", lambda v: f"{v*100:.0f}"),
        ("f4_n_chains", "F4 chains", lambda v: f"{v:.1f}"),
        ("f4_success_rate", "F4 succ %", lambda v: f"{v*100:.0f}"),
        ("f4_gap_median_min", "gap min", lambda v: f"{v:.0f}"),
        ("f4_auto_downtime_h", "auto dt h", lambda v: f"{v:.1f}"),
        ("f4_manual_downtime_h", "manual dt h", lambda v: f"{v:.1f}"),
        ("infra_degraded_h", "deg h", lambda v: f"{v:.1f}"),
        ("corr_top_switch_share", "corr sw %", lambda v: f"{v*100:.0f}"),
    ]

    def comparison_rows(self) -> List[List[str]]:
        agg = self.aggregate()
        header = ["scenario"] + [label for _, label, _ in self._COLUMNS]
        rows = [header]
        for sc in self.scenarios:
            row = [sc.name]
            for key, _, fmt in self._COLUMNS:
                v = agg[sc.name].get(key)
                row.append(fmt(v) if v is not None else "—")
            rows.append(row)
        ref = ["paper"]
        for key, _, fmt in self._COLUMNS:
            v = PAPER_REFERENCE.get(key)
            ref.append(fmt(v) if v is not None else "—")
        rows.append(ref)
        return rows

    def comparison_table(self) -> str:
        """Plain-text table (also valid GitHub markdown)."""
        rows = self.comparison_rows()
        widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
        def line(r):
            return "| " + " | ".join(c.ljust(w) for c, w in zip(r, widths)) \
                + " |"
        sep = "|" + "|".join("-" * (w + 2) for w in widths) + "|"
        return "\n".join([line(rows[0]), sep] + [line(r) for r in rows[1:]])

    def to_markdown(self) -> str:
        n_campaigns = len(self.outcomes)
        parts = [
            "# Scenario sweep report",
            "",
            f"{len(self.scenarios)} scenarios x {len(self.seeds)} seeds = "
            f"{n_campaigns} campaigns, wall time {self.wall_s:.1f} s "
            f"({self.wall_s / max(n_campaigns, 1):.2f} s/campaign).",
            "",
            "## F1-F4 comparison (mean over seeds)",
            "",
            self.comparison_table(),
            "",
            "`—` = not applicable (F1 columns need `telemetry_days > 0`; "
            "F2 columns need `storage_fabric=True`; downtime columns need "
            "at least one episode of that kind).",
            "",
        ]
        parts += self._distribution_section()
        parts += self._f2_section()
        parts += self._control_section()
        parts += [
            "## Scenarios",
            "",
        ]
        for sc in self.scenarios:
            parts.append(f"- **{sc.name}** ({sc.duration_days:.0f} d, "
                         f"{sc.n_nodes} nodes): {sc.description}")
        parts += [
            "",
            "## Paper reference",
            "",
            "F1: 10/10 detection, 2/10 pre-XID, 0.84 FP/day (Table 9). "
            "F3: >50% of exclusions on 3 nodes (Figs 11-13). "
            "F4: 33.3% auto-retry chain success vs 12.5% manual, 11 min "
            "median gap, 1.9 h vs 3.3 h median downtime (Table 14, "
            "Figs 16-17).",
            "",
        ]
        return "\n".join(parts)

    # findings that get distributional columns (metric, label, scale, fmt);
    # F2 columns are deterministic fabric queries — identical across seeds
    _DIST_COLUMNS = [
        ("occupancy", "occ %", 100.0, "{:.1f}"),
        ("goodput", "goodput %", 100.0, "{:.1f}"),
        ("f1_detection_rate", "F1 det %", 100.0, "{:.0f}"),
        ("f1_fp_per_day", "F1 fp/d", 1.0, "{:.2f}"),
        ("f3_top3_share", "F3 top3 %", 100.0, "{:.0f}"),
        ("f4_success_rate", "F4 succ %", 100.0, "{:.0f}"),
        ("f4_gap_median_min", "F4 gap min", 1.0, "{:.1f}"),
        ("f4_auto_downtime_h", "auto dt h", 1.0, "{:.2f}"),
        ("f4_manual_downtime_h", "manual dt h", 1.0, "{:.2f}"),
        ("infra_degraded_h", "deg h", 1.0, "{:.2f}"),
        ("corr_top_switch_share", "corr sw %", 100.0, "{:.0f}"),
        ("ctrl_ttd_h", "TTD h", 1.0, "{:.2f}"),
        ("ctrl_false_drains", "false drains", 1.0, "{:.1f}"),
        ("ctrl_switch_attr_rate", "sw attr %", 100.0, "{:.0f}"),
    ]

    # distributional columns render from this many seeds up — the shared
    # module-level cutoff (kept as a class attribute for back-compat)
    MIN_SEEDS_FOR_DISTRIBUTION = MIN_DIST_SEEDS

    @staticmethod
    def _dist_cell(st: Optional[dict], scale: float, fmt: str) -> str:
        if st is None:
            return "—"
        med = fmt.format(st["median"] * scale)
        q25 = fmt.format(st["q25"] * scale)
        q75 = fmt.format(st["q75"] * scale)
        half = fmt.format((st["ci_hi"] - st["ci_lo"]) / 2 * scale)
        return f"{med} [{q25}, {q75}] ±{half}"

    def _distribution_section(self) -> List[str]:
        """Median / IQR / 95%-CI columns over the seed axis — the
        distributional form of the F1-F4 findings that the Monte Carlo
        mode exists to produce."""
        if len(self.seeds) < self.MIN_SEEDS_FOR_DISTRIBUTION:
            return []
        dist = self.distribution()
        cols = [c for c in self._DIST_COLUMNS
                if any(c[0] in dist[sc.name] for sc in self.scenarios)]
        if not cols:
            return []
        parts = [
            f"## Distributional findings ({len(self.seeds)} seeds)",
            "",
            "Cells are `median [q25, q75] ±half-width` of the normal-"
            "approximation 95% CI of the mean.  The paper's headline "
            "numbers are single-trajectory point estimates; these columns "
            "say how wide each one actually is across seeds.",
            "",
            "| scenario | " + " | ".join(label for _, label, _, _ in cols)
            + " |",
            "|---" * (len(cols) + 1) + "|",
        ]
        for sc in self.scenarios:
            row = [sc.name]
            for key, _, scale, fmt in cols:
                row.append(self._dist_cell(dist[sc.name].get(key),
                                           scale, fmt))
            parts.append("| " + " | ".join(row) + " |")
        parts.append("")
        return parts

    def _f2_section(self) -> List[str]:
        """Bandwidth-vs-node-count curves for fabric-backed scenarios: the
        paper's scale-emergent F2 phenomenon, derived — near-linear at 2-4
        nodes, collapsed to 21.5% read / 16.0% write at 60-node scale."""
        fab_scenarios = [sc for sc in self.scenarios if sc.storage_fabric]
        if not fab_scenarios:
            return []
        parts = ["## F2 storage fabric: aggregate bandwidth vs node count",
                 ""]
        for sc in fab_scenarios:
            fab = sc.fabric()
            parts.append(f"**{sc.name}** (server max "
                         f"{sc.storage_server_read_gbs:.0f}/"
                         f"{sc.storage_server_write_gbs:.0f} GB/s r/w):")
            parts.append("")
            parts.append("| nodes | read GB/s | read util | write GB/s | "
                         "write util |")
            parts.append("|---|---|---|---|---|")
            reads = fab.scaling_curve("read")
            writes = fab.scaling_curve("write")
            for r, w in zip(reads, writes):
                parts.append(
                    f"| {r['nodes']} | {r['aggregate_gbs']:.0f} | "
                    f"{r['utilization']*100:.1f}% | "
                    f"{w['aggregate_gbs']:.0f} | "
                    f"{w['utilization']*100:.1f}% |")
            parts.append("")
        parts.append("Paper F2: restart loads 21.5% of the 700 GB/s read "
                     "max, save bursts 16.0% of the 250 GB/s write max at "
                     "60-node scale; 2-4-node tests show none of this.")
        parts.append("")
        return parts

    # Scenario fields that a control preset legitimately differs from its
    # reactive twin on — everything else must match for a goodput delta to
    # be attributable to the control plane rather than config drift
    _CONTROL_ONLY_FIELDS = frozenset({
        "name", "description", "control_plane", "control_urgent_checkpoint",
        "control_drain", "control_drain_confirm_alarms",
        "control_alarm_memory_h", "log_channel", "blast_radius_aware",
        "telemetry", "telemetry_store", "telemetry_pad_metrics",
    })

    def _reactive_twin(self, ctl_sc: Scenario) -> Optional[Scenario]:
        """The non-control scenario in this sweep whose config matches
        ``ctl_sc`` on every axis the control plane doesn't own — the only
        baseline whose goodput delta isolates the control plane."""
        want = {k: v for k, v in ctl_sc.to_dict().items()
                if k not in self._CONTROL_ONLY_FIELDS}
        for sc in self.scenarios:
            if sc.control_plane:
                continue
            have = {k: v for k, v in sc.to_dict().items()
                    if k not in self._CONTROL_ONLY_FIELDS}
            if have == want:
                return sc
        return None

    def _control_section(self) -> List[str]:
        """Detection->recovery ledger for control-plane scenarios: goodput
        vs the config-matched reactive baseline on identical failure
        schedules, plus the counterfactual accounting (lost-work hours
        avoided per true positive, urgent-save hours wasted per false
        positive)."""
        agg = self.aggregate()
        ctl_scenarios = [sc for sc in self.scenarios
                         if agg[sc.name].get("ctrl_n_alarms") is not None]
        if not ctl_scenarios:
            return []
        parts = ["## Detection -> recovery (control plane)", ""]
        parts.append("Δ goodput is shown only against a config-matched "
                     "non-control scenario in this sweep (identical "
                     "failure schedules, same seeds); `—` means no such "
                     "baseline was swept.  At >= "
                     f"{self.MIN_SEEDS_FOR_DISTRIBUTION} seeds the Δ is "
                     "the paired per-seed distribution: `mean±CI95 "
                     "[q25, q75]`.")
        parts.append("")
        per_seed = {(o.scenario, o.seed): o.findings
                    for o in self.outcomes}
        parts.append("| scenario | goodput % | Δ goodput h (vs) | alarms | "
                      "TP | FP/day | urgent saves | saved h/TP | "
                      "wasted h/FP | drains | crashes dodged | "
                      "log alarms | TTD h | false drains |")
        parts.append("|---|---|---|---|---|---|---|---|---|---|---|"
                     "---|---|---|")

        def cell(a, key, fmt):
            v = a.get(key)
            return fmt.format(v) if v is not None else "—"

        for sc in ctl_scenarios:
            a = agg[sc.name]
            baseline = self._reactive_twin(sc)
            deltas = []
            if baseline is not None:
                hours = sc.duration_days * 24.0
                for seed in self.seeds:
                    g_ctl = per_seed.get((sc.name, seed), {}).get("goodput")
                    g_rea = per_seed.get((baseline.name, seed),
                                         {}).get("goodput")
                    if g_ctl is not None and g_rea is not None:
                        deltas.append((g_ctl - g_rea) * hours)
            if deltas:
                mean = float(np.mean(deltas))
                if len(deltas) >= self.MIN_SEEDS_FOR_DISTRIBUTION:
                    half = 1.96 * float(np.std(deltas, ddof=1)) \
                        / np.sqrt(len(deltas))
                    q25, q75 = (q + 0.0 for q          # -0.0 -> 0.0
                                in np.percentile(deltas, [25, 75]))
                    delta_s = (f"{mean:+.1f}±{half:.1f} "
                               f"[{q25:+.1f}, {q75:+.1f}] "
                               f"({baseline.name})")
                else:
                    delta_s = f"{mean:+.1f} ({baseline.name})"
            else:
                delta_s = "—"
            parts.append(
                f"| {sc.name} | {cell(a, 'goodput', '{:.1%}')} | {delta_s} | "
                f"{cell(a, 'ctrl_n_alarms', '{:.0f}')} | "
                f"{cell(a, 'ctrl_tp', '{:.1f}')} | "
                f"{cell(a, 'ctrl_fp_per_day', '{:.2f}')} | "
                f"{cell(a, 'ctrl_n_urgent_saves', '{:.0f}')} | "
                f"{cell(a, 'ctrl_avoided_per_tp_h', '{:.2f}')} | "
                f"{cell(a, 'ctrl_wasted_per_fp_h', '{:.3f}')} | "
                f"{cell(a, 'ctrl_n_drains', '{:.1f}')} | "
                f"{cell(a, 'ctrl_failures_avoided', '{:.1f}')} | "
                f"{cell(a, 'ctrl_n_log_alarms', '{:.0f}')} | "
                f"{cell(a, 'ctrl_ttd_h', '{:.2f}')} | "
                f"{cell(a, 'ctrl_false_drains', '{:.1f}')} |")
        parts += [
            "",
            "Urgent checkpoints are trajectory-preserving (accounting at "
            "the alarm time, priced like a regular gang-fanin save), so "
            "their goodput delta is exactly `lost-work avoided − save time "
            "spent`.  Predictive drains change the trajectory: a true "
            "positive dodges the crash (and its retry chain) for the price "
            "of a controlled restart; a false positive burns the restart "
            "and a spare for the recheck window.",
            "",
            "`log alarms` counts alarms originating from the log channel "
            "(L4 template/burst verdicts; zero unless `log_channel` is "
            "on).  `TTD h` is the median time-to-detection from fault "
            "onset (precursor start / window open) to the first alarm on "
            "the fault's node; `false drains` counts executed drains with "
            "no fault activity near the drained node.  Compare "
            "`log-fusion` against `log-fusion-off` for the log channel's "
            "deltas.",
            "",
        ]
        return parts

    def write(self, path) -> str:
        md = self.to_markdown()
        with open(path, "w") as f:
            f.write(md)
        return md


class SweepRunner:
    """Runs M scenarios x N seeds and aggregates findings.

    ``executor``: "process" (default — campaigns are CPU-bound pure Python/
    numpy), "thread", or "serial" (in-process, deterministic ordering, used
    by tests).  Under "process", campaigns with a compiled
    ``detector_backend`` still run in this process: they call JAX, and
    one process holds the device.

    ``mc_seeds``: Monte Carlo mode.  ``mc_seeds=N`` overrides ``seeds``
    with ``range(N)`` and routes every scenario through one
    `BatchedCampaignEngine` pass instead of one executor task per seed —
    the per-seed findings are identical (the engine's parity contract),
    the wall clock is a fraction, and the report's distributional columns
    light up.  The F1 telemetry sub-campaigns (``telemetry_days > 0``)
    stay per-seed — a retained 30 s x ~300-metric store per seed is
    memory-bound, not compute-bound — so Monte Carlo sweeps are designed
    for the F2-F4 + goodput findings first.

    ``wavefront_backend``: how Monte Carlo campaigns simulate.  "auto"
    (default) stacks every control-free scenario with the same node count
    into ONE compiled device pass (`run_findings_grid`) when the lane
    count clears the compiled floor, and falls back to the numpy engine
    otherwise; "numpy" forces the stacked-numpy wavefront everywhere;
    "xla"/"pallas" force the compiled core for every eligible scenario
    (control-plane scenarios still run numpy — the sweep mixes presets,
    so an eligibility error would make the flag unusable).  Findings are
    bitwise identical across all of these.
    """

    def __init__(self, scenarios: Sequence[Union[Scenario, str]],
                 seeds: Iterable[int] = (0, 1, 2),
                 max_workers: Optional[int] = None,
                 executor: str = "process",
                 mc_seeds: Optional[int] = None,
                 wavefront_backend: str = "auto"):
        self.scenarios = [get_scenario(s) if isinstance(s, str) else s
                          for s in scenarios]
        names = [s.name for s in self.scenarios]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate scenario names: {names}")
        self.seeds = list(range(mc_seeds)) if mc_seeds is not None \
            else list(seeds)
        self.mc_seeds = mc_seeds
        self.max_workers = max_workers
        if executor not in ("process", "thread", "serial"):
            raise ValueError(f"unknown executor {executor!r}")
        self.executor = executor
        if wavefront_backend not in ("auto", "numpy", "xla", "pallas"):
            raise ValueError(
                f"unknown wavefront backend {wavefront_backend!r}")
        self.wavefront_backend = wavefront_backend

    def run(self) -> SweepResult:
        if self.mc_seeds is not None:
            return self._run_mc()
        tasks = [(sc.to_dict(), seed)
                 for sc in self.scenarios for seed in self.seeds]
        t0 = time.perf_counter()
        # one process holds the device: a campaign whose detector runs a
        # compiled backend calls JAX, so it stays in this process, and
        # only numpy-only campaigns go to pooled children
        def in_parent(spec: dict) -> bool:
            return self.executor == "serial" or (
                self.executor == "process"
                and spec["detector_backend"] != "numpy")
        local = [t for t in tasks if in_parent(t[0])]
        pooled = [t for t in tasks if not in_parent(t[0])]
        if not pooled:
            raw = [run_campaign(d, s) for d, s in local]
        else:
            workers = self.max_workers or min(len(pooled),
                                              os.cpu_count() or 1)
            if self.executor == "process":
                # spawned children start from a fresh interpreter, never
                # from a fork of a parent whose JAX runtime may be live
                pool = concurrent.futures.ProcessPoolExecutor(
                    max_workers=workers,
                    mp_context=multiprocessing.get_context("spawn"))
            else:
                pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=workers)
            with pool:
                futs = [pool.submit(run_campaign, d, s) for d, s in pooled]
                raw = [run_campaign(d, s) for d, s in local]
                raw += [f.result() for f in futs]
        wall = time.perf_counter() - t0
        order = {sc.name: i for i, sc in enumerate(self.scenarios)}
        outcomes = sorted(
            (SweepOutcome(r["scenario"], r["seed"], r["findings"])
             for r in raw),
            key=lambda o: (order[o.scenario], o.seed))
        return SweepResult(scenarios=self.scenarios, seeds=self.seeds,
                           outcomes=outcomes, wall_s=wall)

    def _grid_pass(self) -> Dict[int, List[dict]]:
        """Whole-sweep wavefront: stack every eligible (scenario, seed)
        lane of the Monte Carlo sweep into single compiled device passes
        (one per node count — gang masks share the node axis) and return
        ``scenario_index -> per-seed findings`` for the covered subset."""
        backend = self.wavefront_backend
        if backend == "numpy":
            return {}
        from repro.kernels.common import WAVEFRONT_MIN_SEEDS
        from repro.kernels.wavefront import compiled_eligible
        from repro.kernels.wavefront.ops import run_findings_grid
        cfgs = [sc.to_campaign_config(0) for sc in self.scenarios]
        groups: Dict[int, List[int]] = {}
        for i, cfg in enumerate(cfgs):
            if compiled_eligible(cfg):
                groups.setdefault(cfg.n_nodes, []).append(i)
        dev = "xla" if backend == "auto" else backend
        out: Dict[int, List[dict]] = {}
        t_g = time.perf_counter()
        for idxs in groups.values():
            if backend == "auto" \
                    and len(idxs) * len(self.seeds) < WAVEFRONT_MIN_SEEDS:
                continue                 # too few lanes to beat numpy
            per_cfg = run_findings_grid([cfgs[i] for i in idxs],
                                        self.seeds, backend=dev)
            for j, i in enumerate(idxs):
                out[i] = per_cfg[j]
        self._grid_per_campaign = (time.perf_counter() - t_g) \
            / max(len(out) * len(self.seeds), 1)
        return out

    def _run_mc(self) -> SweepResult:
        """Monte Carlo path: one stacked pass per scenario — through the
        whole-sweep compiled grid where eligible, the batched numpy
        engine otherwise (identical findings either way)."""
        from repro.core.batch import BatchedCampaignEngine
        t0 = time.perf_counter()
        grid = self._grid_pass()
        eng_backend = "numpy" if self.wavefront_backend == "numpy" \
            else "auto"
        outcomes: List[SweepOutcome] = []
        for si, sc in enumerate(self.scenarios):
            t_sc = time.perf_counter()
            if si in grid:
                findings_list = grid[si]
            else:
                engine = BatchedCampaignEngine(
                    sc.to_campaign_config(0),
                    wavefront_backend=eng_backend)
                findings_list = engine.run_findings(self.seeds)
            f2 = _f2_findings(sc) if sc.storage_fabric else None
            for seed, findings in zip(self.seeds, findings_list):
                if f2:
                    findings.update(f2)
                if sc.telemetry_days > 0:
                    findings.update(_f1_findings(sc, seed))
                outcomes.append(SweepOutcome(sc.name, seed, findings))
            # shared average, stamped after the (possibly F1-dominated)
            # per-seed work so it matches what the pool path reports;
            # grid-covered scenarios add their share of the device pass
            per_campaign = (time.perf_counter() - t_sc) \
                / max(len(self.seeds), 1)
            if si in grid:
                per_campaign += self._grid_per_campaign
            for findings in findings_list:
                findings["wall_s"] = per_campaign
        wall = time.perf_counter() - t0
        return SweepResult(scenarios=self.scenarios, seeds=self.seeds,
                           outcomes=outcomes, wall_s=wall)
