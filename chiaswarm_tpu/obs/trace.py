"""Dapper-style trace spans: where a job's wall-clock actually goes.

One :class:`JobTrace` per hive job, built across the worker's thread
boundaries (poll loop -> slot task -> executor thread -> upload task),
answering the question the ROADMAP's "fast as the hardware allows"
north star keeps asking: poll wait vs host prep vs denoise vs decode vs
upload, per job, with real numbers.

Mechanics:

- Durations come from ``time.perf_counter()`` **only** — wall clock
  (``time.time``) jumps under NTP and is banned for durations by the
  swarmlint R8 ``wallclock-duration`` rule. One wall-clock stamp is
  taken per trace as export *metadata* (when did this happen), never
  subtracted.
- Within one thread, :func:`span` nests via a ``contextvars`` context
  variable: the executor activates a job's trace once at entry
  (:meth:`JobTrace.active`) and every ``span()`` below — pipeline
  encode, lane wait, decode — attaches at the right depth with no
  plumbing.
- Across threads/tasks the handoff is explicit: the trace object rides
  the job dict (``node/worker.py`` attaches it at poll receipt under
  ``TRACE_KEY``; the executor pops it before argument formatting) and
  phases are opened/closed manually (:meth:`JobTrace.phase`).
- Finished traces land in a bounded in-memory :class:`TraceRing`,
  exported as Perfetto/chrome-tracing JSON by ``/debug/traces``
  (node/worker.py) — load the body at https://ui.perfetto.dev.

One primitive, two clocks: every live :class:`Span` — a phase, a
``span()``, the job's root — also holds a profiler ``TraceAnnotation``
named ``swarm.<name>`` (through ``core/compat.trace_annotation``,
resolved once) from its creation to its ``end()``. A name in a job's
span tree and a name in the profiler's host plane are therefore the
same thing by construction: a device-idle gap in an XLA trace reads in
the vocabulary ``/debug/traces`` prints. The annotation is free outside
an active capture (one C++ enabled-check). Spans built from explicit
stamps after the fact (:meth:`Span.child_at`) are on the job's clock
only — the profiler cannot be told about the past.

Everything is stdlib plus that lazy compat lookup; a ``span()`` outside
any active trace times into a detached throwaway Span and still
annotates, so library code (the lane driver thread, the worker's poll
loop) can instrument unconditionally (allocation-light: one small
object per span, none per lookup).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import os
import threading
import time
from typing import Any, Iterator

#: key under which a JobTrace rides a job/result dict between worker
#: stages. Executors MUST pop it before kwargs formatting and the
#: worker pops it before JSON-serializing an envelope.
TRACE_KEY = "_obs_trace"

ENV_RING_CAPACITY = "CHIASWARM_TRACE_RING"

_CURRENT: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "chiaswarm_obs_span", default=None)

#: prefix of every span's name on the profiler's clock
ANNOTATION_PREFIX = "swarm."

_annotation_cls: Any = None


def _annotate(name: str) -> Any:
    """Enter a profiler annotation ``swarm.<name>``; None when it cannot
    be had (observability never fails the job it observes)."""
    global _annotation_cls
    try:
        if _annotation_cls is None:
            from chiaswarm_tpu.core import compat

            _annotation_cls = compat.trace_annotation
        annotation = _annotation_cls(ANNOTATION_PREFIX + name)
        annotation.__enter__()
        return annotation
    except Exception:
        return None


class Span:
    """One timed region; children nest. Durations on perf_counter; a
    live span (no explicit ``t1``) is also a profiler annotation."""

    __slots__ = ("name", "meta", "t0", "t1", "children", "_annotation")

    def __init__(self, name: str, meta: dict[str, Any] | None = None,
                 t0: float | None = None, t1: float | None = None) -> None:
        self.name = str(name)
        self.meta = dict(meta or {})
        self.t0 = time.perf_counter() if t0 is None else float(t0)
        self.t1: float | None = None if t1 is None else float(t1)
        self.children: list[Span] = []
        self._annotation = _annotate(self.name) \
            if t0 is None and t1 is None else None

    def child(self, name: str, **meta: Any) -> "Span":
        span = Span(name, meta)
        self.children.append(span)
        return span

    def child_at(self, name: str, t0: float, t1: float,
                 **meta: Any) -> "Span":
        """A closed child from two ``perf_counter`` stamps taken
        elsewhere (the lane reports a job's time inside it this way):
        on the job's clock only, never annotated."""
        span = Span(name, meta, t0=t0, t1=max(float(t0), float(t1)))
        self.children.append(span)
        return span

    def end(self) -> None:
        """Close this span (idempotent); still-open children close at
        the same instant so a crashed region never exports negative or
        unbounded durations."""
        if self.t1 is None:
            self.t1 = time.perf_counter()
        annotation, self._annotation = self._annotation, None
        if annotation is not None:
            try:
                annotation.__exit__(None, None, None)
            except Exception:
                pass
        for child in self.children:
            if child.t1 is None:
                child.t1 = self.t1
                child.end()

    @property
    def open(self) -> bool:
        return self.t1 is None

    @property
    def duration_s(self) -> float:
        end = time.perf_counter() if self.t1 is None else self.t1
        return max(0.0, end - self.t0)

    def find(self, name: str) -> "Span | None":
        """First span named ``name`` in this subtree (tests/debugging)."""
        if self.name == name:
            return self
        for child in self.children:
            hit = child.find(name)
            if hit is not None:
                return hit
        return None

    def to_dict(self) -> dict[str, Any]:
        data: dict[str, Any] = {
            "name": self.name,
            "start_us": int(self.t0 * 1e6),
            "duration_us": int(self.duration_s * 1e6),
        }
        if self.meta:
            data["meta"] = {k: v for k, v in self.meta.items()}
        if self.children:
            data["children"] = [c.to_dict() for c in self.children]
        return data


@contextlib.contextmanager
def span(name: str, **meta: Any) -> Iterator[Span]:
    """Time a region under the currently active span (contextvar), and
    name it ``swarm.<name>`` on the profiler's clock for as long.

    With no active trace the span is detached and discarded (the
    annotation still lands in a recording XLA trace) — safe to sprinkle
    through library code unconditionally. In a coroutine, wrap the wait
    itself: a task's context does not nest across its awaits the way a
    thread's stack does."""
    parent = _CURRENT.get()
    current = parent.child(name, **meta) if parent is not None \
        else Span(name, meta)
    token = _CURRENT.set(current)
    try:
        yield current
    finally:
        _CURRENT.reset(token)
        current.end()


def current_span() -> Span | None:
    return _CURRENT.get()


class JobTrace:
    """Span tree for one job, handed explicitly across worker stages.

    Top-level *phases* (poll / execute / upload) are children of the
    root, opened with :meth:`phase` — starting a phase closes the
    previous one, so the manual cross-thread bookkeeping can never leak
    an open span. Library spans attach below whatever phase is open via
    :meth:`active` + :func:`span`.
    """

    def __init__(self, name: str = "job", **meta: Any) -> None:
        self.root = Span(name, meta)
        # wall-clock ANCHOR for humans reading exports ("when was
        # this"); durations never touch it (swarmlint R8)
        self.started_at_unix = time.time()
        self.finished = False
        # monotone ring sequence number, assigned by TraceRing.push():
        # the /debug/traces?since=<seq> cursor key (0 = never pushed)
        self.seq = 0

    @property
    def meta(self) -> dict[str, Any]:
        return self.root.meta

    def phase(self, name: str, **meta: Any) -> Span:
        """Open a new top-level phase, closing any open predecessor."""
        for child in self.root.children:
            if child.open:
                child.end()
        return self.root.child(name, **meta)

    def gap(self, name: str, **meta: Any) -> Span | None:
        """Name what the open phase has not named yet: a closed child
        from the later of the phase's start and its last closed child's
        end to NOW. The worker's hand-overs between threads are read
        this way, from stamps that are already there (``handover``: the
        executor thread's first act; ``result.wait``: the upload task's)
        — on the job's clock only (see :meth:`Span.child_at`)."""
        phase = next((c for c in reversed(self.root.children) if c.open),
                     None)
        if phase is None:
            return None
        since = max([phase.t0] + [c.t1 for c in phase.children
                                  if c.t1 is not None])
        return phase.child_at(name, since, time.perf_counter(), **meta)

    def tail(self) -> Span:
        """Deepest open span — where library spans should attach."""
        node = self.root
        while node.children and node.children[-1].open:
            node = node.children[-1]
        return node

    @contextlib.contextmanager
    def active(self) -> Iterator[Span]:
        """Make this trace the thread/task's ambient span target."""
        token = _CURRENT.set(self.tail())
        try:
            yield self.root
        finally:
            _CURRENT.reset(token)

    def finish(self, ring: "TraceRing | None" = None) -> None:
        """Close the tree and publish it (idempotent)."""
        if self.finished:
            return
        self.finished = True
        self.root.end()
        (ring if ring is not None else TRACE_RING).push(self)

    # ---- export ----

    def to_dict(self) -> dict[str, Any]:
        return {"started_at_unix": round(self.started_at_unix, 6),
                "seq": self.seq,
                "root": self.root.to_dict()}

    def to_chrome_events(self, pid: int = 1,
                         tid: int = 1) -> list[dict[str, Any]]:
        """Chrome-tracing "complete" (ph=X) events, microsecond ts on
        the process perf_counter timebase — Perfetto-loadable."""
        events: list[dict[str, Any]] = []

        def emit(node: Span) -> None:
            event = {
                "name": node.name,
                "ph": "X",
                "ts": int(node.t0 * 1e6),
                "dur": max(1, int(node.duration_s * 1e6)),
                "pid": pid,
                "tid": tid,
            }
            if node.meta:
                event["args"] = {k: str(v) for k, v in node.meta.items()}
            events.append(event)
            for child in node.children:
                emit(child)

        emit(self.root)
        return events


def _span_count(node: Span) -> int:
    return 1 + sum(_span_count(child) for child in node.children)


class TraceRing:
    """Bounded ring of recently finished traces (newest last).

    Every pushed trace gets a monotone ``seq``; evictions are COUNTED
    (``spans_evicted`` feeds ``chiaswarm_trace_spans_evicted_total``)
    and the ``?since=<seq>`` cursor on ``/debug/traces`` lets a scraper
    detect — rather than silently lose — traces the ring dropped
    between scrapes: if ``cursor.oldest_seq > since + 1``, the gap is
    exactly the evicted window."""

    def __init__(self, capacity: int | None = None) -> None:
        if capacity is None:
            capacity = int(os.environ.get(ENV_RING_CAPACITY, "128") or 128)
        self.capacity = max(1, int(capacity))
        self._lock = threading.Lock()
        self._traces: collections.deque[JobTrace] = collections.deque(
            maxlen=self.capacity)
        self._seq = 0
        self.traces_evicted = 0
        self.spans_evicted = 0

    def push(self, trace: JobTrace) -> None:
        with self._lock:
            self._seq += 1
            trace.seq = self._seq
            if len(self._traces) == self.capacity:
                oldest = self._traces[0]  # deque maxlen drops it below
                self.traces_evicted += 1
                self.spans_evicted += _span_count(oldest.root)
            self._traces.append(trace)

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)

    def traces(self, since: int | None = None) -> list[JobTrace]:
        """Ring contents, oldest first; ``since`` keeps only traces
        pushed after that sequence number (the scrape cursor)."""
        with self._lock:
            out = list(self._traces)
        if since is not None:
            out = [t for t in out if t.seq > int(since)]
        return out

    def cursor(self) -> dict[str, Any]:
        """Scraper bookkeeping: pass ``last_seq`` back as ``?since=``;
        a later ``oldest_seq`` > since + 1 means the ring evicted
        traces the scraper never saw (count in ``evicted_spans``)."""
        with self._lock:
            return {
                "last_seq": self._seq,
                "oldest_seq": self._traces[0].seq if self._traces else None,
                "evicted_traces": self.traces_evicted,
                "evicted_spans": self.spans_evicted,
            }

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()

    def to_dicts(self, since: int | None = None) -> list[dict[str, Any]]:
        return [t.to_dict() for t in self.traces(since)]

    def to_chrome(self, since: int | None = None) -> dict[str, Any]:
        """One Perfetto-loadable document; each trace gets its own tid
        so jobs render as separate tracks."""
        events: list[dict[str, Any]] = []
        for tid, trace in enumerate(self.traces(since), start=1):
            events.extend(trace.to_chrome_events(tid=tid))
        return {"traceEvents": events, "displayTimeUnit": "ms"}


#: process-global ring ``/debug/traces`` reads; workers may substitute
#: their own (hermetic tests) via the ``ring=`` parameter on finish().
TRACE_RING = TraceRing()


def job_trace(job: dict[str, Any] | None) -> JobTrace | None:
    """The trace riding ``job`` (or a result envelope), if any."""
    if not isinstance(job, dict):
        return None
    trace = job.get(TRACE_KEY)
    return trace if isinstance(trace, JobTrace) else None


def attach(job: dict[str, Any], trace: JobTrace) -> None:
    job[TRACE_KEY] = trace


def detach(job: dict[str, Any] | None) -> JobTrace | None:
    """Pop the trace off a job/result dict (before kwargs formatting or
    JSON serialization)."""
    if not isinstance(job, dict):
        return None
    trace = job.pop(TRACE_KEY, None)
    return trace if isinstance(trace, JobTrace) else None


@contextlib.contextmanager
def activate(trace: JobTrace | None) -> Iterator[JobTrace | None]:
    """``trace.active()`` that tolerates None (jobs without traces —
    directly-injected test jobs, replayed dead letters)."""
    if trace is None:
        yield None
        return
    with trace.active():
        yield trace
