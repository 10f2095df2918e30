"""Host-side span tracer: one jsonl event stream, zero device impact.

``SpanTracer.span("train_step")`` times a host-side phase and appends one
``{"kind": "span", ...}`` record on exit.  The tracer never touches a
jax.Array and is never called from inside a jitted function, so enabling
it adds zero device syncs and zero extra jit traces — the design point
that makes it safe to leave on in production serving loops (the pjit-at-
scale practice of structured *host* telemetry, PAPERS.md "Scalable
Training of Language Models using JAX pjit and TPUv4").

``NULL_TRACER`` is the disabled implementation: ``span()`` returns a
shared ``nullcontext``, so instrumented code pays one attribute lookup
and one function call when telemetry is off.  Code under instrumentation
takes a tracer instance (trainer, serving engine) rather than consulting
a global, so two engines in one process can write disjoint streams.
"""

from __future__ import annotations

import collections
import contextlib
import json
import math
import os
import threading
import time
import types


def jsonable(record: dict) -> dict:
    """NaN/Inf are not valid JSON (json.dumps emits bare NaN tokens strict
    parsers reject — exactly in the diverged-run case where telemetry
    matters most); serialize them as null."""
    return {
        k: (None if isinstance(v, float) and not math.isfinite(v) else v)
        for k, v in record.items()
    }


def append_jsonl(path: str, record: dict, truncate: bool = False) -> None:
    """The one way every telemetry writer puts a record on disk: one
    jsonable object, one line, open-write-close per record — crash-safe
    (every line lands flushed+closed), and all writers are O(ms+) host
    phases so the syscall pair is noise.  ``truncate`` starts a fresh
    stream (writers defer it to their first write so a checkpoint resume
    can preserve history)."""
    with open(path, "w" if truncate else "a") as f:
        f.write(json.dumps(jsonable(record)) + "\n")


class SpanTracer:
    """Appends span/event records to one jsonl file.

    Span records carry the name, start offset from tracer creation
    (``t_ms``), duration (``dur_ms``), nesting ``depth`` and enclosing
    ``parent`` span name (per-thread stacks, so the async checkpoint
    thread can't corrupt the trainer's nesting), plus any keyword
    attributes given at the call site.  Writes are lock-serialized,
    open-append-close per record — crash-safe, and these are O(ms+)
    host phases so the syscall pair is noise.

    The first write additionally stamps one ``trace_header`` record
    (``wall_t0_s``: the wall clock paired with the tracer's t=0, plus
    the pid), which is what lets ``obs/export.py`` merge streams from
    different replicas/processes onto one timeline — ``t_ms`` alone is
    a process-local perf_counter offset and not comparable.

    ``jsonl_path=None`` keeps the tracer live with no file behind it —
    the ring-only mode a remote worker runs in when the controller
    drains its records over the wire (``obs_pull``) instead of the
    operator collecting files by hand.

    ``ring_len > 0`` additionally keeps the last N records in a
    bounded in-memory ring, each stamped with a monotonically
    increasing sequence number.  ``ring_pull(cursor)`` drains it
    incrementally — the cursor-resume idea of the PR-5 replay RPC
    applied to telemetry: a reader that comes back with its last
    cursor gets exactly the records it missed (or an explicit
    ``dropped`` count when the ring lapped it).  The ring holds
    already-jsonable dicts, so pulled records are byte-identical to
    what the file (if any) received.

    ``rotate_bytes > 0`` caps the jsonl file: when appending a record
    would push the file past the cap, the current file rolls to
    ``<path>.1`` (one generation — the previous ``.1`` is dropped) and
    a fresh ``trace_header`` opens the new file so each generation
    stays independently alignable.  ``obs/export.load_jsonl`` reads
    the rolled pair oldest-first.
    """

    enabled = True

    def __init__(self, jsonl_path: str | None = None,
                 _clock=time.perf_counter, *, ring_len: int = 0,
                 rotate_bytes: int = 0):
        if ring_len < 0:
            raise ValueError(f"ring_len must be >= 0, got {ring_len}")
        if rotate_bytes < 0:
            raise ValueError(
                f"rotate_bytes must be >= 0 (0 = no rotation), got "
                f"{rotate_bytes}"
            )
        if jsonl_path:
            parent = os.path.dirname(jsonl_path)
            if parent:
                os.makedirs(parent, exist_ok=True)
        self.jsonl_path = jsonl_path
        self.rotate_bytes = rotate_bytes
        # ring of (seq, jsonable record); None when disabled
        self._ring = (collections.deque(maxlen=ring_len)
                      if ring_len else None)
        self._seq = 0
        # file size accounting for rotation; resolved lazily at the
        # first file write (a preserved-history append starts from the
        # existing file's size, a truncating first write from 0)
        self._file_bytes: int | None = None
        self._clock = _clock
        self._t0 = _clock()
        # wall clock paired with _t0 at the same instant: t_ms offsets
        # are perf_counter deltas (monotonic, but process-local), so
        # streams from different replicas/processes — or a post-resume
        # rebuilt tracer — are only comparable through this epoch.  The
        # first write stamps it as a "trace_header" record, and
        # obs/export.py aligns N streams on their headers' wall clocks.
        self.wall_t0 = time.time()
        self._lock = threading.Lock()
        self._local = threading.local()
        # small stable per-tracer thread index, stamped as ``tid`` on
        # span/event records: spans from different host threads (the
        # async checkpoint thread vs the trainer loop) overlap in wall
        # time without nesting, so the exporter must give each thread
        # its own track — overlapping slices on one track are invalid
        # trace-event JSON that Perfetto drops
        self._tids: dict[int, int] = {}
        # truncation is deferred to the first write (same contract as
        # MetricsLogger) so a checkpoint resume / --auto-restart rebuild
        # can preserve the pre-crash span history — which is exactly the
        # stream a post-mortem needs.  NB ``t_ms`` offsets restart from 0
        # for the new tracer's records (under a fresh header, so the
        # exporter still places them correctly on the shared timeline).
        self._truncate_pending = True
        self._header_pending = True

    def _stack(self) -> list[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time the enclosed host-side block as one span record.  Yields a
        handle whose ``attrs`` dict is the record's: what the block learns
        while it runs (a launch's counters, once its fetch has returned) it
        sets there before the span closes."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        stack.append(name)
        t_start = self._clock()
        try:
            yield types.SimpleNamespace(attrs=attrs)
        finally:
            dur = self._clock() - t_start
            stack.pop()
            record = {
                "kind": "span",
                "name": name,
                "t_ms": round((t_start - self._t0) * 1000, 3),
                "dur_ms": round(dur * 1000, 3),
                "depth": len(stack),
                "tid": self._tid(),
            }
            if parent is not None:
                record["parent"] = parent
            if attrs:
                record.update(attrs)
            self.write(record)

    def event(self, name: str, **attrs) -> None:
        """Record a point-in-time marker (no duration)."""
        record = {
            "kind": "event",
            "name": name,
            "t_ms": round((self._clock() - self._t0) * 1000, 3),
            "tid": self._tid(),
        }
        if attrs:
            record.update(attrs)
        self.write(record)

    def preserve_history(self) -> None:
        """Keep the existing stream (called on checkpoint resume)."""
        self._truncate_pending = False

    def _header_record(self) -> dict:
        return {"kind": "trace_header",
                "wall_t0_s": round(self.wall_t0, 6),
                "pid": os.getpid()}

    def _emit(self, record: dict, truncate: bool = False) -> None:
        """Lock held: one record into the ring and (if any) the file."""
        record = jsonable(record)
        if self._ring is not None:
            self._ring.append((self._seq, record))
            self._seq += 1
        if not self.jsonl_path:
            return
        line = json.dumps(record) + "\n"
        if self._file_bytes is None:
            self._file_bytes = (
                0 if truncate or not os.path.exists(self.jsonl_path)
                else os.path.getsize(self.jsonl_path)
            )
        if (self.rotate_bytes > 0 and self._file_bytes > 0
                and self._file_bytes + len(line) > self.rotate_bytes):
            # roll the full generation aside (one generation kept) and
            # re-head the fresh file so it stays alignable on its own —
            # the header does NOT enter the ring again (pulled streams
            # already carry the original one)
            os.replace(self.jsonl_path, self.jsonl_path + ".1")
            header = json.dumps(jsonable(self._header_record())) + "\n"
            with open(self.jsonl_path, "w") as f:
                f.write(header)
            self._file_bytes = len(header)
        with open(self.jsonl_path, "w" if truncate else "a") as f:
            f.write(line)
        self._file_bytes = (len(line) if truncate
                            else self._file_bytes + len(line))

    def write(self, record: dict) -> None:
        with self._lock:
            if self._header_pending:
                self._header_pending = False
                self._emit(self._header_record(),
                           truncate=self._truncate_pending)
                self._truncate_pending = False
            self._emit(record, truncate=self._truncate_pending)
            self._truncate_pending = False

    def ring_pull(self, cursor: int = 0, limit: int = 4096) -> dict:
        """Drain ring records with seq >= ``cursor`` (bounded).

        Returns ``{"records": [...], "cursor": next_cursor,
        "dropped": n}`` — ``dropped`` counts records that aged out of
        the ring before this pull (the reader's cursor fell behind the
        ring's oldest resident seq).  A tracer with no ring returns an
        empty page at the caller's cursor.
        """
        with self._lock:
            if self._ring is None:
                return {"records": [], "cursor": cursor, "dropped": 0}
            dropped = 0
            if self._ring:
                oldest = self._ring[0][0]
                if cursor < oldest:
                    dropped = oldest - cursor
                    cursor = oldest
            out = [rec for seq, rec in self._ring
                   if seq >= cursor][:max(0, limit)]
            return {"records": out, "cursor": cursor + len(out),
                    "dropped": dropped}


class _NullTracer:
    """Telemetry off: every operation is a no-op."""

    enabled = False
    _ctx = contextlib.nullcontext()  # reusable + reentrant

    def span(self, name: str, **attrs):
        return self._ctx

    def event(self, name: str, **attrs) -> None:
        pass

    def preserve_history(self) -> None:
        pass

    def write(self, record: dict) -> None:
        pass

    def ring_pull(self, cursor: int = 0, limit: int = 4096) -> dict:
        return {"records": [], "cursor": cursor, "dropped": 0}


NULL_TRACER = _NullTracer()


class _AnnotatedSpan:
    """A tracer's span and a profiler annotation, entered and left as one."""

    __slots__ = ("_span", "_annotation")

    def __init__(self, span, annotation):
        self._span, self._annotation = span, annotation

    def __enter__(self):
        self._annotation.__enter__()
        return self._span.__enter__()

    def __exit__(self, *exc):
        try:
            return self._span.__exit__(*exc)
        finally:
            self._annotation.__exit__(*exc)


class AnnotatedTracer:
    """Puts a tracer's spans on the profiler's clock as well.

    ``span(name, **attrs)`` opens the wrapped tracer's span and a
    ``jax.profiler.TraceAnnotation(name)``, so a ``jax.profiler`` capture of
    the trainer or a serving worker shows ``serving_tick``, ``serving_admit``,
    ``data_load`` ... on the host's line, above the device's operations.
    Spans named ``step_span`` open a ``StepTraceAnnotation("train",
    step_num=attrs["step"])`` instead: the profiler's per-step analysis keys
    on it.  With no profiler session an annotation is a flag test (some
    hundred ns; PERF.md has the measurement).  Everything else (``event``,
    ``write``, ``ring_pull``, ``enabled`` ...) is the wrapped tracer's own.

    ``annotated(tracer)`` is how ``Trainer`` and ``ServingEngine`` take
    their tracer; it wraps once.  JAX is imported here, on first use, so
    that ``obs`` stays importable by processes that must stay off it.
    """

    def __init__(self, inner, step_span: str | None = None):
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        self._inner = inner
        self._step_span = step_span
        self._annotation, self._step_annotation = (
            TraceAnnotation, StepTraceAnnotation)

    def span(self, name: str, **attrs):
        span = self._inner.span(name, **attrs)  # may raise: nothing is open
        if name == self._step_span:
            annotation = self._step_annotation(
                "train", step_num=attrs.get("step", 0))
        else:
            annotation = self._annotation(name)
        return _AnnotatedSpan(span, annotation)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def annotated(tracer, step_span: str | None = None):
    """``tracer`` under an ``AnnotatedTracer`` (once, however often asked)."""
    if isinstance(tracer, AnnotatedTracer):
        return tracer
    return AnnotatedTracer(tracer, step_span)
