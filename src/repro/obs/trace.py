"""Low-overhead span tracer with Chrome-trace export.

Spans are context managers around the runtime's hot seams (cohort staging,
H2D, dispatch, fold, state-table write, eval, checkpoint). Design goals:

  * zero-cost when disabled — ``Tracer.span`` returns a shared no-op
    context manager singleton (``NULL_SPAN``) without allocating,
  * thread-safe — spans are opened from the main loop, the population's
    prefetch producer, and the async state-writer thread; completed
    records land in a bounded ``deque`` ring buffer,
  * monotonic clocks — ``time.perf_counter_ns`` throughout; wall time
    never enters a record, so traces are comparable across restarts.

Per-thread nesting depth is tracked with a ``threading.local`` stack so
exports can reconstruct parent/child structure (the async window nests
h2d inside stage inside the dispatch fill loop).

A span opened without a round index ``t`` takes the ``t`` of the span
it nests in, so helpers that do not know the round (the cohort draw, a
host read of results) still carry the identifier every span of one round
shares.

Export targets the Chrome trace-event JSON format (complete events,
``ph: "X"``) loadable in ``chrome://tracing`` / Perfetto, validated by
:func:`validate_chrome_trace`. When ``annotate=True`` each span also
enters a ``jax.profiler.TraceAnnotation`` named ``repro.<kind>`` (the
prefix keeps a program span apart from a caller's annotation of the same
name), so spans line up with XLA activity on the device trace's clock
inside a profiler capture (:func:`start_profiler` / :func:`stop_profiler`);
the Chrome export keeps the bare kind.

>>> tr = Tracer(enabled=True)
>>> with tr.span("stage", t=0):
...     with tr.span("h2d"):
...         pass
>>> [(r.kind, r.depth, r.attrs["t"]) for r in tr.records()]
[('h2d', 1, 0), ('stage', 0, 0)]
>>> Tracer(enabled=False).span("stage") is NULL_SPAN
True
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time


class _NullSpan:
    """Shared no-op context manager: the disabled-tracer fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()

#: canonical span kinds instrumented across the runtime (docs/observability.md)
SPAN_KINDS = ("round", "select", "cold-start", "stage", "h2d", "dispatch",
              "sync", "fold", "state-write", "eval", "checkpoint", "lease",
              "heartbeat")

#: prefix of a span's name in a profiler capture (``annotate=True``)
ANNOTATION_PREFIX = "repro."


class SpanRecord:
    """One completed span: monotonic start/duration in ns + context."""
    __slots__ = ("kind", "start_ns", "dur_ns", "tid", "depth", "attrs")

    def __init__(self, kind, start_ns, dur_ns, tid, depth, attrs):
        self.kind = kind
        self.start_ns = start_ns
        self.dur_ns = dur_ns
        self.tid = tid
        self.depth = depth
        self.attrs = attrs

    def __repr__(self):  # pragma: no cover - debugging aid
        return (f"SpanRecord({self.kind!r}, dur={self.dur_ns / 1e6:.3f}ms, "
                f"depth={self.depth}, attrs={self.attrs})")


class _Span:
    __slots__ = ("_tracer", "kind", "attrs", "_start", "_annot")

    def __init__(self, tracer, kind, attrs):
        self._tracer = tracer
        self.kind = kind
        self.attrs = attrs
        self._start = 0
        self._annot = None

    def __enter__(self):
        tr = self._tracer
        stack = tr._stack()
        if stack and "t" not in self.attrs:
            t = stack[-1].attrs.get("t")
            if t is not None:
                # a copy: ``wrap`` hands every call the same attrs dict
                self.attrs = {**self.attrs, "t": t}
        stack.append(self)
        if tr.annotate:
            import jax
            self._annot = jax.profiler.TraceAnnotation(
                ANNOTATION_PREFIX + self.kind)
            self._annot.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        tr = self._tracer
        if self._annot is not None:
            self._annot.__exit__(*exc)
        stack = tr._stack()
        # tolerate a foreign pop (mis-nesting) rather than corrupting depth
        if stack and stack[-1] is self:
            stack.pop()
        depth = len(stack)
        tr._records.append(SpanRecord(
            self.kind, self._start - tr.epoch_ns, end - self._start,
            threading.get_ident(), depth, self.attrs))
        return False


class Tracer:
    """Thread-safe span tracer over a bounded ring buffer.

    ``capacity`` bounds memory: the oldest records are dropped once the
    ring is full (``deque(maxlen=...)`` — appends are atomic under the
    GIL, so producer/writer threads need no extra lock).
    """

    def __init__(self, enabled: bool = False, capacity: int = 65536,
                 annotate: bool = False):
        self.enabled = bool(enabled)
        self.annotate = bool(annotate)
        self.capacity = int(capacity)
        self.epoch_ns = time.perf_counter_ns()
        self._records = collections.deque(maxlen=self.capacity)
        self._local = threading.local()

    # -- recording ------------------------------------------------------
    def span(self, kind: str, **attrs):
        """Open a span; returns ``NULL_SPAN`` (no allocation) when disabled."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, kind, attrs)

    def wrap(self, kind: str, fn, **attrs):
        """Wrap ``fn`` so every call runs inside a ``kind`` span.

        The enabled check happens per call, so a tracer enabled after
        executors were built still records their dispatches.
        """
        def wrapped(*args, **kwargs):
            if not self.enabled:
                return fn(*args, **kwargs)
            with _Span(self, kind, attrs):
                return fn(*args, **kwargs)
        wrapped.__wrapped__ = fn
        return wrapped

    def _stack(self):
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def open_depth(self) -> int:
        """Open (unclosed) spans on the *calling* thread — 0 when balanced."""
        return len(self._stack())

    # -- inspection -----------------------------------------------------
    def records(self):
        """Snapshot of completed spans (oldest first)."""
        return list(self._records)

    def clear(self):
        self._records.clear()
        self.epoch_ns = time.perf_counter_ns()

    def stage_totals(self) -> dict:
        """Aggregate per-kind timing: {kind: {count, total_s, max_s}}."""
        out = {}
        for r in self._records:
            agg = out.setdefault(r.kind, {"count": 0, "total_s": 0.0,
                                          "max_s": 0.0})
            s = r.dur_ns / 1e9
            agg["count"] += 1
            agg["total_s"] += s
            if s > agg["max_s"]:
                agg["max_s"] = s
        return out

    def round_totals(self) -> dict:
        """Per-round attributed time: {t: seconds} over spans with a ``t``
        attr (stage/fold/eval carry the round index)."""
        out = {}
        for r in self._records:
            t = r.attrs.get("t")
            if t is None or r.depth > 0:   # count top-level spans only
                continue
            out[int(t)] = out.get(int(t), 0.0) + r.dur_ns / 1e9
        return out

    # -- export ---------------------------------------------------------
    def chrome_events(self) -> list:
        """Records as Chrome trace-event complete events (``ph: "X"``)."""
        pid = os.getpid()
        events = []
        for r in self._records:
            ev = {"name": r.kind, "cat": "repro", "ph": "X",
                  "ts": r.start_ns / 1e3, "dur": r.dur_ns / 1e3,
                  "pid": pid, "tid": r.tid}
            if r.attrs:
                ev["args"] = {k: v for k, v in r.attrs.items()}
            events.append(ev)
        return events


def chrome_trace_doc(events: list) -> dict:
    """Wrap events in the JSON object format Perfetto expects."""
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome_trace(path: str, tracer: Tracer) -> dict:
    """Atomically write the tracer's records as a Chrome trace JSON file."""
    doc = chrome_trace_doc(tracer.chrome_events())
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return doc


def validate_chrome_trace(doc) -> list:
    """Validate a trace document against the trace-event schema subset we
    emit. Returns a list of error strings (empty = valid)."""
    errors = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["trace document must be an object with a 'traceEvents' key"]
    events = doc["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be a list"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        for key in ("name", "ph", "ts", "pid", "tid"):
            if key not in ev:
                errors.append(f"event {i}: missing required key {key!r}")
        ph = ev.get("ph")
        if ph not in ("X", "B", "E", "i", "C", "M"):
            errors.append(f"event {i}: unknown phase {ph!r}")
        if ph == "X" and "dur" not in ev:
            errors.append(f"event {i}: complete event missing 'dur'")
        for key in ("ts", "dur"):
            if key in ev and (not isinstance(ev[key], (int, float))
                              or ev[key] < 0):
                errors.append(f"event {i}: {key!r} must be a number >= 0")
        if "args" in ev and not isinstance(ev["args"], dict):
            errors.append(f"event {i}: 'args' must be an object")
    return errors


# -- programmatic jax.profiler hooks ------------------------------------
_PROFILING = False


def start_profiler(log_dir: str):
    """Start a programmatic ``jax.profiler`` capture into ``log_dir``."""
    global _PROFILING
    import jax
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    _PROFILING = True


def stop_profiler():
    """Stop the capture started by :func:`start_profiler` (idempotent)."""
    global _PROFILING
    if not _PROFILING:
        return
    import jax
    jax.profiler.stop_trace()
    _PROFILING = False
