"""Synthetic operational log channel (L4-style diagnosis).

The paper's failure clusters were jointly diagnosed from operational logs
*and* Prometheus metrics; this package models the log side:

* :mod:`refsim.logs.emitter` — a structured synthetic log emitter driven by
  the sim's failure schedule and session lifecycle (XID lines, NCCL/RPC
  errors, retry-chain output, storage stalls, background noise).
* :mod:`refsim.logs.analysis` — an L4-style analysis pass: template
  extraction (tokenize -> variable masking -> template IDs), per-template
  burst + rarity scoring, and cross-node correlation that attributes a
  gang-wide error burst to one root-cause node (Mycroft-style).

`ControlPlane` fuses the analyzer's verdicts with the metric detector's
robust-stats vote behind the ``log_channel`` config gate (off by default;
see docs/LOG_CHANNEL.md).
"""
from refsim.logs.emitter import (  # noqa: F401
    LogEmitter, LogLine, RNG_STREAM_LOGS,
)
from refsim.logs.analysis import (  # noqa: F401
    LogAnalyzer, LogChannelConfig, LogVerdict,
)
