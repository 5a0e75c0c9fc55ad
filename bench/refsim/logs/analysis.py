"""L4-style log analysis: templates, burst/rarity scoring, attribution.

Pipeline (one pass, streaming, deterministic):

1. **Template extraction** — tokenize each line, mask digit-bearing and
   hex tokens to ``<*>``, intern the masked string as a template ID.  The
   level (first token) sets the template's base weight (ERROR 3, WARN 1,
   INFO 0); ``node-<id>`` references are captured *before* masking as
   cross-node attribution edges.
2. **Burst + rarity scoring** — lines bucket into fixed absolute windows
   of ``window_h``.  A template *qualifies* in a window when its count
   beats ``max(min_lines, burst_factor * rate * window_h)`` against its
   own historical rate baseline (burst), or when it is a near-unseen
   ERROR template (rarity).  Qualifying weight is boosted by rarity:
   ``level_w * (1 + rarity_boost / sqrt(1 + hist))``.
3. **Cross-node correlation** — qualifying line weight accrues to the
   *emitting* node, and ``ref_weight``-scaled weight to every *referenced*
   node (Mycroft-style: a gang-wide NCCL burst on 58 peers that all name
   ``node-17`` indicts node 17, not the 58 symptomatic peers).  A window
   yields at most one verdict: the top node, if its score clears
   ``min_score`` and ``dominance`` times the runner-up.

Windows are only scored once *complete* (fully covered by ingested
chunks); a trailing partial window is buffered for the next chunk, so
chunk boundaries — which differ between event spans but are mirrored
exactly between the scalar and batched engines — never change verdicts.
The first ``warmup_h`` hours only warm the baselines (cold-start guard:
with empty baselines every template would "burst" in window zero).
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_MASK = re.compile(r"\S*\d\S*")
_REF = re.compile(r"node-(\d+)")
_KEEP = re.compile(r"[a-z]+")

_LEVEL_W = {"ERROR": 3.0, "WARN": 1.0}

_NET_KEYS = ("nfs", "rpc", "transport", "backlog", "retransmit")
_RES_KEYS = ("memory", "oom", "allocation", "reclaim", "cgroup")


def _class_of(masked: str) -> str:
    """Template class for alarm routing: ``net`` | ``res`` | ``node``."""
    t = masked.lower()
    if any(k in t for k in _NET_KEYS):
        return "net"
    if any(k in t for k in _RES_KEYS):
        return "res"
    return "node"


def _slug_of(masked: str) -> str:
    words = _KEEP.findall(masked.lower())[1:]      # drop the level token
    return "-".join(words)[:48] or "line"


@dataclass(frozen=True)
class LogChannelConfig:
    """Knobs for the log analysis pass (defaults tuned so steady noise
    never verdicts while fault programs verdict within one window)."""
    window_h: float = 0.25          # scoring window (absolute grid)
    warmup_h: float = 1.0           # baseline-only cold start
    min_lines: int = 2              # floor count for a burst
    burst_factor: float = 4.0       # count vs rate-baseline multiple
    rare_error_max: int = 8         # ERROR templates rarer than this
                                    #   qualify without bursting
    rarity_boost: float = 3.0       # weight boost ~ 1/sqrt(1 + hist)
    ref_weight: float = 1.0         # cross-node reference edge weight
    min_score: float = 6.0          # verdict floor
    dominance: float = 2.0          # top node vs runner-up ratio
    noise_per_node_h: float = 1.0   # emitter-side background chatter rate


@dataclass
class LogVerdict:
    """One window's root-cause attribution."""
    time_h: float                   # earliest contributing line on the node
    node: int
    score: float
    # (template name "log:<cls>:<slug>", contribution) — weight-sorted
    top: List[Tuple[str, float]] = field(default_factory=list)


class _Template:
    __slots__ = ("tid", "name", "cls", "level_w", "hist")

    def __init__(self, tid: int, masked: str):
        self.tid = tid
        self.cls = _class_of(masked)
        self.name = f"log:{self.cls}:{_slug_of(masked)}"
        self.level_w = _LEVEL_W.get(masked.split(" ", 1)[0], 0.0)
        self.hist = 0               # lifetime line count (rate baseline)


class LogAnalyzer:
    """Streaming template store + window scorer.  Feed it each chunk's
    lines via :meth:`ingest`; it returns the verdicts for every window
    the new chunk completed."""

    def __init__(self, config: Optional[LogChannelConfig] = None):
        self.cfg = config or LogChannelConfig()
        self._templates: Dict[str, _Template] = {}
        self._by_id: List[_Template] = []
        # parsed-but-unscored lines: (time_h, node, tid, refs)
        self._pending: List[tuple] = []
        self._scored_until = 0.0    # absolute time scored through

    @property
    def n_templates(self) -> int:
        return len(self._by_id)

    def template(self, text: str) -> _Template:
        masked = _MASK.sub("<*>", text)
        tmpl = self._templates.get(masked)
        if tmpl is None:
            tmpl = _Template(len(self._by_id), masked)
            self._templates[masked] = tmpl
            self._by_id.append(tmpl)
        return tmpl

    def ingest(self, lines, t1: float) -> List[LogVerdict]:
        """Parse ``lines`` (the chunk covering up to time ``t1``) and score
        every window that is now complete."""
        for ln in lines:
            refs = tuple(int(r) for r in _REF.findall(ln.text))
            self._pending.append(
                (ln.time_h, ln.node, self.template(ln.text).tid, refs))
        w = self.cfg.window_h
        m_end = int(math.floor(t1 / w + 1e-9))     # windows [0, m_end) done
        if m_end * w <= self._scored_until:
            return []
        ready: Dict[int, List[tuple]] = defaultdict(list)
        keep: List[tuple] = []
        for rec in self._pending:
            m = int(rec[0] / w)
            (ready[m] if m < m_end else keep).append(rec)
        self._pending = keep
        verdicts: List[LogVerdict] = []
        for m in sorted(ready):
            v = self._score_window(m, ready[m])
            if v is not None:
                verdicts.append(v)
        self._scored_until = m_end * w
        return verdicts

    def _score_window(self, m: int, recs: List[tuple]) -> \
            Optional[LogVerdict]:
        cfg = self.cfg
        w = cfg.window_h
        counts: Dict[int, int] = defaultdict(int)
        for rec in recs:
            counts[rec[2]] += 1
        verdict = None
        if m * w >= cfg.warmup_h - 1e-9:
            hours_before = max(m * w, w)
            weight: Dict[int, float] = {}
            for tid, c in counts.items():
                tmpl = self._by_id[tid]
                if tmpl.level_w <= 0.0:
                    continue                        # INFO never qualifies
                rate = tmpl.hist / hours_before
                burst = c >= max(cfg.min_lines, cfg.burst_factor * rate * w)
                rare_err = (tmpl.level_w >= 3.0
                            and tmpl.hist < cfg.rare_error_max)
                if burst or rare_err:
                    weight[tid] = tmpl.level_w * (
                        1.0 + cfg.rarity_boost / math.sqrt(1.0 + tmpl.hist))
            verdict = self._attribute(recs, weight) if weight else None
        for tid, c in counts.items():               # baselines after scoring
            self._by_id[tid].hist += c
        return verdict

    def _attribute(self, recs: List[tuple],
                   weight: Dict[int, float]) -> Optional[LogVerdict]:
        cfg = self.cfg
        score: Dict[int, float] = defaultdict(float)
        contrib: Dict[int, Dict[int, float]] = defaultdict(
            lambda: defaultdict(float))
        first: Dict[int, float] = {}
        for t, node, tid, refs in recs:
            wt = weight.get(tid)
            if wt is None:
                continue
            if node >= 0:
                score[node] += wt
                contrib[node][tid] += wt
                first[node] = min(first.get(node, t), t)
            for r in refs:
                if r != node and r >= 0:
                    score[r] += cfg.ref_weight * wt
                    contrib[r][tid] += cfg.ref_weight * wt
                    first[r] = min(first.get(r, t), t)
        if not score:
            return None
        # deterministic argmax: score desc, node asc on ties
        best = min(score, key=lambda nd: (-score[nd], nd))
        top_score = score[best]
        runner_up = max((s for nd, s in score.items() if nd != best),
                        default=0.0)
        if top_score < cfg.min_score or top_score < cfg.dominance * runner_up:
            return None
        top = sorted(contrib[best].items(), key=lambda kv: (-kv[1], kv[0]))
        return LogVerdict(
            time_h=first[best], node=best, score=top_score,
            top=[(self._by_id[tid].name, s) for tid, s in top[:5]])
