"""Request-lifecycle tracer: Chrome-trace / Perfetto JSON span recording.

The span taxonomy (docs/OBSERVABILITY.md) follows one request through the
engine: arrival → ``admit`` (with its ``prefill_chunk`` dispatches) →
``decode_chunk``s (``decode_dispatch`` then ``decode_sync``) each followed
by ``emit`` → retire, with the request's whole lifetime, from its arrival,
drawn as an async span keyed by uid.

Overhead contract:

  * timestamps are host ``perf_counter_ns``; tracing adds zero device
    round-trips and must not change ``host_syncs_per_token``;
  * recording one span is two clock reads, one ``TraceAnnotation`` and one
    list append — no serialization until ``save()``;
  * a disabled tracer (``enabled=False``) short-circuits to a no-op
    context manager; an engine with no tracer pays one branch per site.

When ``annotate_xla=True`` (default) every synchronous span also enters a
``jax.profiler.TraceAnnotation`` with the same name, so host spans line up
with XLA device traces when a ``jax.profiler.trace()`` session is active.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

__all__ = ["Tracer", "validate_chrome_trace", "load_trace"]


class Tracer:
    """Append-only span/event recorder emitting Chrome-trace JSON.

    Event kinds used (Chrome Trace Event Format):
      * ``X`` complete spans (``span()`` context manager / ``complete()``
        for intervals the caller already timed),
      * ``i`` instants (``instant()``),
      * ``b``/``e`` async spans (``async_begin``/``async_end``) for request
        lifetimes that interleave across chunk boundaries.

    Nesting is tracked per thread; ``span()`` enforces stack discipline by
    construction (context manager), which is exactly the invariant Perfetto
    requires of same-track complete events.
    """

    def __init__(self, *, enabled: bool = True, annotate_xla: bool = True,
                 process_name: str = "repro-serve", pid: Optional[int] = None):
        self.enabled = enabled
        self.process_name = process_name
        self.pid = os.getpid() if pid is None else int(pid)
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._t0 = time.perf_counter_ns()
        self._ann_cls = None
        if annotate_xla:
            from jax.profiler import TraceAnnotation
            self._ann_cls = TraceAnnotation
        if enabled:
            self._meta("process_name", {"name": process_name})

    # -- clock ------------------------------------------------------------
    def now_ns(self) -> int:
        return time.perf_counter_ns()

    def _us(self, t_ns: int) -> float:
        return (t_ns - self._t0) / 1e3

    def _tid(self) -> int:
        tid = getattr(self._tls, "tid", None)
        if tid is None:
            tid = threading.get_ident() & 0x7FFFFFFF
            self._tls.tid = tid
            self._meta("thread_name",
                       {"name": threading.current_thread().name}, tid=tid)
        return tid

    def _depth(self) -> int:
        return getattr(self._tls, "depth", 0)

    # -- recording --------------------------------------------------------
    def _meta(self, name: str, args: dict, tid: int = 0):
        with self._lock:
            self._events.append({"name": name, "ph": "M", "pid": self.pid,
                                 "tid": tid, "ts": 0, "args": args})

    def _emit(self, ev: Dict[str, Any]):
        with self._lock:
            self._events.append(ev)

    @contextmanager
    def span(self, name: str, cat: str = "engine", **attrs):
        """Synchronous complete span; nests per thread (stack discipline)."""
        if not self.enabled:
            yield
            return
        ann = self._ann_cls(name) if self._ann_cls is not None else None
        if ann is not None:
            ann.__enter__()
        self._tls.depth = self._depth() + 1
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            self._tls.depth = self._depth() - 1
            if ann is not None:
                ann.__exit__(None, None, None)
            self._emit({"name": name, "ph": "X", "cat": cat,
                        "pid": self.pid, "tid": self._tid(),
                        "ts": self._us(t0), "dur": (t1 - t0) / 1e3,
                        "args": attrs})

    def complete(self, name: str, t0_ns: int, t1_ns: int,
                 cat: str = "engine", **attrs):
        """Record an interval the caller already timed (both ends captured
        at existing sync points) — no extra clock reads on the hot path."""
        if not self.enabled:
            return
        self._emit({"name": name, "ph": "X", "cat": cat, "pid": self.pid,
                    "tid": self._tid(), "ts": self._us(t0_ns),
                    "dur": max(0.0, (t1_ns - t0_ns) / 1e3), "args": attrs})

    def instant(self, name: str, cat: str = "engine", **attrs):
        if not self.enabled:
            return
        self._emit({"name": name, "ph": "i", "s": "t", "cat": cat,
                    "pid": self.pid, "tid": self._tid(),
                    "ts": self._us(time.perf_counter_ns()), "args": attrs})

    def async_begin(self, name: str, id: int, cat: str = "request",
                    ts_ns: Optional[int] = None, **attrs):
        """Open an async span at ``ts_ns`` (perf_counter_ns; default now),
        which may lie in the past: a request's span opens at its arrival
        (at the tracer's start if it arrived before that)."""
        if not self.enabled:
            return
        ts_ns = time.perf_counter_ns() if ts_ns is None else max(ts_ns,
                                                                 self._t0)
        self._emit({"name": name, "ph": "b", "cat": cat, "id": int(id),
                    "pid": self.pid, "tid": self._tid(),
                    "ts": self._us(ts_ns), "args": attrs})

    def async_end(self, name: str, id: int, cat: str = "request", **attrs):
        if not self.enabled:
            return
        self._emit({"name": name, "ph": "e", "cat": cat, "id": int(id),
                    "pid": self.pid, "tid": self._tid(),
                    "ts": self._us(time.perf_counter_ns()), "args": attrs})

    # -- export -----------------------------------------------------------
    def __len__(self):
        with self._lock:
            return len(self._events)

    def chrome_trace(self) -> dict:
        """The Chrome-trace JSON object (Perfetto's legacy-JSON loader)."""
        with self._lock:
            events = list(self._events)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"process": self.process_name}}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


def load_trace(path: str) -> List[dict]:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or not isinstance(doc.get("traceEvents"),
                                                   list):
        raise ValueError(f"{path}: not a Chrome-trace JSON object "
                         "(want {'traceEvents': [...]})")
    return doc["traceEvents"]


def validate_chrome_trace(events: List[dict]) -> dict:
    """Validate events against the Chrome Trace Event Format.

    Checks (the subset Perfetto's legacy JSON importer enforces):
      * every event has ``name``/``ph``/``pid``/``tid``/``ts`` with sane
        types; ``ts``/``dur`` non-negative;
      * ``X`` events carry a ``dur``;
      * async ``b``/``e`` events carry an ``id`` and are balanced per
        (cat, id) with begin <= end timestamps;
      * ``X`` events on one (pid, tid) track nest properly (no partial
        overlap — the stack-discipline invariant).

    Returns summary counts; raises ``ValueError`` on the first violation.
    """
    counts: Dict[str, int] = {}
    async_open: Dict[tuple, List[float]] = {}
    by_track: Dict[tuple, List[dict]] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i}: not an object")
        for field, types in (("name", str), ("ph", str),
                             ("pid", int), ("tid", int),
                             ("ts", (int, float))):
            if not isinstance(ev.get(field), types):
                raise ValueError(f"event {i} ({ev.get('name')!r}): field "
                                 f"{field!r} missing or mistyped: {ev}")
        ph = ev["ph"]
        counts[ph] = counts.get(ph, 0) + 1
        if ev["ts"] < 0:
            raise ValueError(f"event {i} ({ev['name']!r}): negative ts")
        if ph == "X":
            if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
                raise ValueError(f"event {i} ({ev['name']!r}): X event "
                                 f"needs non-negative dur, got {ev.get('dur')!r}")
            by_track.setdefault((ev["pid"], ev["tid"]), []).append(ev)
        elif ph in ("b", "e"):
            if "id" not in ev:
                raise ValueError(f"event {i} ({ev['name']!r}): async "
                                 f"{ph!r} event needs an id")
            key = (ev.get("cat", ""), ev["id"])
            if ph == "b":
                async_open.setdefault(key, []).append(ev["ts"])
            else:
                opens = async_open.get(key)
                if not opens:
                    raise ValueError(f"event {i} ({ev['name']!r}): async end "
                                     f"without begin for id={ev['id']}")
                t_b = opens.pop()
                if ev["ts"] < t_b:
                    raise ValueError(f"event {i} ({ev['name']!r}): async end "
                                     f"ts {ev['ts']} precedes begin {t_b}")
    dangling = {k: v for k, v in async_open.items() if v}
    if dangling:
        raise ValueError(f"unbalanced async spans (begin without end): "
                         f"{sorted(dangling)[:5]}")
    # same-track X events must nest (never partially overlap); tolerance is
    # 1e-3 us (1 ns): abutting spans share a boundary timestamp whose us
    # conversion rounds differently for end-of-previous vs start-of-next
    for (pid, tid), evs in by_track.items():
        evs = sorted(evs, key=lambda e: (e["ts"], -e["dur"]))
        stack: List[tuple] = []  # (end_ts, name)
        for ev in evs:
            t0, t1 = ev["ts"], ev["ts"] + ev["dur"]
            while stack and stack[-1][0] <= t0 + 1e-3:
                stack.pop()
            if stack and t1 > stack[-1][0] + 1e-3:
                raise ValueError(
                    f"span {ev['name']!r} [{t0:.1f}, {t1:.1f}]us on track "
                    f"({pid}, {tid}) partially overlaps enclosing "
                    f"{stack[-1][1]!r} (ends {stack[-1][0]:.1f}us): "
                    "X events on one track must nest")
            stack.append((t1, ev["name"]))
    return {"events": len(events), "by_phase": counts,
            "tracks": len(by_track)}
