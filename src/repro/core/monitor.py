"""Real-time timing / throughput monitoring (paper §4 "future developments":
real-time tracking of timing and resource usage — implemented here).

Lock-protected counters and timers that every kernel pool updates in
place; ``report()`` renders one dict for logging / EXPERIMENTS.

``Monitor.span(name)`` is how the program times a section of code
(``timer(name).add`` records a time summed some other way): it adds
the section's time to the ``Timer`` of that name, keeps a per-thread stack
of open spans so each timer also knows its self time (its time less that of
the spans opened inside it), and opens a ``jax.profiler.TraceAnnotation``
of the same name.  The annotation costs about a microsecond and records
nothing unless a profiler trace is being taken; when one is, the span sits
on the device trace's clock beside the operations it launched.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

# every span name the program emits (docs/operations.md describes each)
SPAN_NAMES = (
    # exploration fleet: Exchange._step_fleet and FusedEngine.score_after
    "exchange.round",            # one whole fleet-path Exchange.step()
    "exchange.refresh_weights",  # PredictionPool.refresh_weights()
    "exchange.predict",          # WalkerFleet.step() (or engine.score)
    "engine.dispatch",           # state guard, lock, the jitted call
    "engine.wait",               # host blocked on the device's result
    "engine.fetch_selected",     # selected rows sliced to the host
    "exchange.oracle_put",       # selected rows queued for the oracles
    "exchange.min_interval",     # the iteration floor's sleep
    # committee retraining: CommitteeTrainer.train
    "trainer.round",             # one whole train() round
    "trainer.dispatch",          # one fused step: lock, key, jitted call
    "trainer.sync",              # the round-end host copy of the metrics
    # threaded runtime (core/runtime.py)
    "manager.fresh_score",       # dynamic_oracle_list re-scoring
    "oracle.run_calc",           # one oracle call
    "train.retrain",             # one trainer-loop round
)


class Timer:
    """Totals for a repeatedly timed section: ``total`` seconds over
    ``count`` calls, the longest call, and ``self_total``, the part of
    ``total`` spent outside child spans."""

    def __init__(self):
        self.total = 0.0
        self.self_total = 0.0
        self.count = 0
        self.max = 0.0
        self._lock = threading.Lock()

    def add(self, dt: float, self_dt: Optional[float] = None):
        with self._lock:
            self.total += dt
            self.self_total += dt if self_dt is None else self_dt
            self.count += 1
            self.max = max(self.max, dt)

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def stats(self) -> Dict[str, float]:
        return {"mean_s": self.mean, "max_s": self.max, "count": self.count,
                "total_s": self.total, "self_s": self.self_total}


class Monitor:
    """Named timers + counters for the whole PAL run."""

    def __init__(self):
        self._timers: Dict[str, Timer] = collections.defaultdict(Timer)
        self._counters: Dict[str, int] = collections.defaultdict(int)
        self._lock = threading.Lock()
        self._local = threading.local()
        self.start_time = time.time()

    def timer(self, name: str) -> Timer:
        with self._lock:
            return self._timers[name]

    @contextlib.contextmanager
    def span(self, name: str, **args) -> Iterator[None]:
        """Time the enclosed section into ``timer(name)``; ``args`` (such
        as the step number) go on the profiler's event."""
        stack: List[float] = self._local.__dict__.setdefault("stack", [])
        stack.append(0.0)               # time of this span's children
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name, **args):
                yield
        finally:
            dt = time.perf_counter() - t0
            children = stack.pop()
            if stack:
                stack[-1] += dt
            self.timer(name).add(dt, dt - children)

    def incr(self, name: str, n: int = 1):
        with self._lock:
            self._counters[name] += n

    def count(self, name: str) -> int:
        with self._lock:
            return self._counters[name]

    def report(self) -> Dict[str, object]:
        with self._lock:
            return {
                "uptime_s": time.time() - self.start_time,
                "timers": {k: t.stats() for k, t in self._timers.items()},
                "counters": dict(self._counters),
            }
