"""Session abstraction — paper §3.2 (Table 6).

A session is the stateful unit of training lifecycle management: it bundles
nodes, storage, and checkpoint progress.  Containers are stateless; sessions
resume from the last checkpoint.  The FSM mirrors Backend.AI's states with
the hang-timeout semantics of Appendix A.1 (PREPARING <= 1 h,
TERMINATING <= 30 min).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from enum import Enum
from typing import List, Optional


class SessionState(Enum):
    PENDING = "PENDING"
    SCHEDULED = "SCHEDULED"
    PREPARING = "PREPARING"      # image pull / NCCL init / data+ckpt load
    RUNNING = "RUNNING"
    TERMINATING = "TERMINATING"
    TERMINATED = "TERMINATED"
    ERROR = "ERROR"
    CANCELLED = "CANCELLED"


# legal transitions (anything -> ERROR is implicit on failure)
_TRANSITIONS = {
    SessionState.PENDING: {SessionState.SCHEDULED, SessionState.CANCELLED},
    SessionState.SCHEDULED: {SessionState.PREPARING, SessionState.CANCELLED},
    SessionState.PREPARING: {SessionState.RUNNING, SessionState.ERROR,
                             SessionState.TERMINATING},
    SessionState.RUNNING: {SessionState.TERMINATING, SessionState.ERROR},
    SessionState.TERMINATING: {SessionState.TERMINATED, SessionState.ERROR},
    SessionState.TERMINATED: set(),
    SessionState.ERROR: set(),
    SessionState.CANCELLED: set(),
}

HANG_TIMEOUTS_H = {SessionState.PREPARING: 1.0, SessionState.TERMINATING: 0.5}

_session_counter = itertools.count()


@dataclass
class Session:
    task_name: str                     # retry chains group by task name
    n_nodes: int
    session_id: int = field(default_factory=lambda: next(_session_counter))
    state: SessionState = SessionState.PENDING
    nodes: List[int] = field(default_factory=list)
    created_h: float = 0.0
    started_h: Optional[float] = None          # entered RUNNING
    ended_h: Optional[float] = None
    checkpoint_step: int = 0                   # resume point
    error: Optional[str] = None
    history: List[tuple] = field(default_factory=list)  # (time_h, state)

    def transition(self, new: SessionState, t_h: float, error: str = None):
        if new is SessionState.ERROR:
            pass                                    # always legal
        elif new not in _TRANSITIONS[self.state]:
            raise ValueError(f"illegal transition {self.state} -> {new}")
        self.state = new
        self.history.append((t_h, new))
        if new is SessionState.RUNNING and self.started_h is None:
            self.started_h = t_h
        if new in (SessionState.TERMINATED, SessionState.ERROR,
                   SessionState.CANCELLED):
            self.ended_h = t_h
        if error:
            self.error = error

    @property
    def reached_training(self) -> bool:
        return any(s is SessionState.RUNNING for _, s in self.history)

    @property
    def is_terminal(self) -> bool:
        return self.state in (SessionState.TERMINATED, SessionState.ERROR,
                              SessionState.CANCELLED)

    def hang_check(self, t_h: float) -> bool:
        """True if the session exceeded its per-state allowed time."""
        limit = HANG_TIMEOUTS_H.get(self.state)
        if limit is None or not self.history:
            return False
        entered = self.history[-1][0]
        return (t_h - entered) > limit

    def elapsed_running_h(self, t_h: float = None) -> float:
        if self.started_h is None:
            return 0.0
        end = self.ended_h if self.ended_h is not None else t_h
        return max(0.0, (end if end is not None else self.started_h)
                   - self.started_h)
