"""Request coalescing: a batching queue over the campaign engine.

Concurrent what-if queries arrive on caller threads; a single dispatcher
thread collects them for a short window (``window_s``), dedupes by
canonical key, hands ONE batch to the runner callable, and demultiplexes
the per-key results back onto each caller's future.  The engine cost of
a window is therefore one stacked pass over the *distinct* scenarios in
it, not one pass per request — the dispatch amortization the service
exists for.

The coalescer is generic: it knows keys, payloads and a runner
``batch -> {key: result}``; what a "pass" means (grouping heterogeneous
configs, seed stacking) lives in the runner (`WhatIfService._run_batch`).
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Tuple

from repro import tracing

__all__ = ["Coalescer"]


class Coalescer:
    """Window-batching queue with per-key dedup.

    ``runner(batch)`` receives ``[(key, payload), ...]`` with distinct
    keys (first payload wins for duplicates submitted in one window) and
    returns ``{key: result}``.  Every future submitted under a key gets
    that key's result; a runner exception fails every future of the
    window.  ``submit`` never blocks on the engine — callers wait on the
    returned future.

    * ``window_s`` — how long the dispatcher collects after the first
      request of a window lands (10-50 ms trades latency for batching).
    * ``max_batch`` — dispatch early once this many requests are queued
      (bounds worst-case batch latency under a thundering herd).

    Each request's queue wait runs from ``submit`` to the dispatch of
    its batch: the window, plus any pass still running when it arrived.
    ``stats()`` reports their count, total and maximum.
    """

    def __init__(self, runner: Callable[[List[Tuple[str, Any]]],
                                        Dict[str, Any]],
                 window_s: float = 0.02, max_batch: int = 64):
        if window_s < 0:
            raise ValueError(f"window_s must be >= 0, got {window_s}")
        self.runner = runner
        self.window_s = float(window_s)
        self.max_batch = int(max_batch)
        self._cv = threading.Condition()
        self._queue: List[Tuple[str, Any, Future, float]] = []
        self._closed = False
        # stats (read without the lock: monotone counters, display only)
        self.n_requests = 0
        self.n_deduped = 0
        self.n_windows = 0
        self.n_dispatched = 0
        self.n_waits = 0
        self.wait_total_s = 0.0
        self.wait_max_s = 0.0
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="whatif-coalescer")
        self._thread.start()

    # -- caller side --------------------------------------------------------

    def submit(self, key: str, payload: Any) -> Future:
        fut: Future = Future()
        with self._cv:
            if self._closed:
                raise RuntimeError("coalescer is closed")
            self._queue.append((key, payload, fut, time.monotonic()))
            self.n_requests += 1
            self._cv.notify()
        return fut

    def close(self) -> None:
        """Stop the dispatcher; queued requests still run (one final
        window), new submissions are rejected."""
        with self._cv:
            self._closed = True
            self._cv.notify()
        self._thread.join(timeout=30.0)

    # -- dispatcher side ----------------------------------------------------

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait()
                if not self._queue and self._closed:
                    return
                # first request opens the window; keep collecting until
                # the deadline or the early-dispatch threshold
                with tracing.span("serve.queue_wait"):
                    deadline = time.monotonic() + self.window_s
                    while len(self._queue) < self.max_batch:
                        left = deadline - time.monotonic()
                        if left <= 0 or self._closed:
                            break
                        self._cv.wait(timeout=left)
                batch, self._queue = self._queue, []
            self._dispatch(batch)

    def _dispatch(self, batch: List[Tuple[str, Any, Future, float]]
                  ) -> None:
        now = time.monotonic()
        waits = [now - t for *_, t in batch]
        distinct: "Dict[str, Any]" = {}
        for key, payload, *_ in batch:
            distinct.setdefault(key, payload)
        self.n_waits += len(waits)
        self.wait_total_s += sum(waits)
        self.wait_max_s = max(self.wait_max_s, max(waits))
        self.n_windows += 1
        self.n_dispatched += len(distinct)
        self.n_deduped += len(batch) - len(distinct)
        try:
            results = self.runner(list(distinct.items()))
        except BaseException as e:                 # noqa: BLE001
            for _, _, fut, _ in batch:
                if not fut.done():
                    fut.set_exception(e)
            return
        for key, _, fut, _ in batch:
            if fut.done():
                continue
            if key in results:
                fut.set_result(results[key])
            else:
                fut.set_exception(KeyError(
                    f"runner returned no result for key {key!r}"))

    def stats(self) -> dict:
        return {"requests": self.n_requests, "windows": self.n_windows,
                "dispatched": self.n_dispatched, "deduped": self.n_deduped,
                "queue_waits": self.n_waits,
                "queue_wait_total_s": self.wait_total_s,
                "queue_wait_max_s": self.wait_max_s,
                "window_s": self.window_s, "max_batch": self.max_batch}
