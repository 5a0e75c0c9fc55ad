"""The per-campaign findings fold and its distribution over seeds.

Copied from the scenario engine's sweep module: ``compute_findings`` on
one scalar ``CampaignResult``.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from refsim.core.failures import CORRELATED_KINDS, INFRA_KINDS
from refsim.core.retry import chain_stats


def _top_switch_share(failures) -> float:
    sw = [f.switch for f in failures if f.kind == "switch_degrade"]
    if not sw:
        return 0.0
    return float(np.bincount(np.asarray(sw)).max() / len(sw))


def compute_findings(res) -> Dict[str, Optional[float]]:
    """F2-F4 metrics (plus campaign health) from one CampaignResult."""
    st = chain_stats(res.retry_chains())
    excl = res.exclusions.summary()
    autos = [d["hours"] for d in res.downtimes
             if d["auto"] and d.get("kind") != "drain"]
    mans = [d["hours"] for d in res.downtimes
            if not d["auto"] and d.get("kind") != "drain"]
    out = {
        "occupancy": res.training_occupancy(),
        "goodput": res.goodput(),
        "n_failures": float(len(res.failures)),
        "n_sessions": float(len(res.sessions)),
        "ckpt_events": float(res.checkpoint_events),
        "mean_lost_h": float(np.mean(res.lost_hours))
        if res.lost_hours else 0.0,
        "f3_top3_share": excl["top3_share"],
        "f3_deliberate_fraction": excl["deliberate_fraction"],
        "f4_n_chains": float(st["n_chains"]),
        "f4_n_attempts": float(st["n_attempts"]),
        "f4_success_rate": st["chain_success_rate"],
        "f4_gap_median_min": st["gap_median_min"],
        "f4_auto_downtime_h": float(np.median(autos)) if autos else None,
        "f4_manual_downtime_h": float(np.median(mans)) if mans else None,
        "infra_n_events": float(sum(1 for f in res.failures
                                    if f.kind in INFRA_KINDS)),
        "infra_degraded_h": float(np.sum(res.degraded_hours)),
        "corr_n_events": float(sum(1 for f in res.failures
                                   if f.kind in CORRELATED_KINDS)),
        "corr_top_switch_share": _top_switch_share(res.failures),
    }
    if res.control is not None:
        ctl = res.control.summarize(res.failures, res.duration_h)
        out.update({f"ctrl_{k}": v for k, v in ctl.items()})
        drain_excl = res.exclusions.by_reason().get("predictive drain")
        out["ctrl_drain_excl_events"] = \
            float(drain_excl["count"]) if drain_excl else 0.0
    return out

