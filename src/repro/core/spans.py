"""Per-batch host spans on the profiler's clock.

A :class:`SpanRecorder` times the stages of one unit of work (a search
batch).  Entering ``recorder.span(name)`` does two things: it opens a
``jax.profiler.TraceAnnotation`` of that name, so the span lands on the
profiler's host plane on the same clock as the device events, and on
exit it adds the span's ``perf_counter`` seconds and a count of 1 (and,
where given, a byte count) to the recorder's totals for the thread that
ran it.  :meth:`SpanRecorder.tally` adds further counts to a span's
entry (the byte-parallel decodes, :func:`note_parallel_decode`).
:meth:`SpanRecorder.totals` merges the threads' totals into the
``{name: {"s": seconds, "n": count[, "bytes": n, ...]}}`` block that
``SearchService.last_trace["spans"]`` carries; the names are declared in
``repro.search.schema.TRACE_SCHEMA["spans"]``, the fields in
``repro.search.schema.SPAN_FIELDS``.

Code below the service (the index's posting decode) finds the batch's
recorder through :func:`current`: a context variable that
:meth:`SpanRecorder.active` sets for the batch and resets after it, and
that a worker thread sees when it runs under ``contextvars.copy_context``.
Outside a batch it is None and nothing is timed.  Nothing here is shared
between two recorders.

A span costs one to two microseconds with the profiler off, so spans
sit at stage, wave, query and lookup grain, never inside a per-row
loop.  jax is imported when a recorder is made, never at import:
``repro.core`` runs without it.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from typing import Dict, Iterator, List, Optional

_CURRENT: contextvars.ContextVar[Optional["SpanRecorder"]] = (
    contextvars.ContextVar("repro_span_recorder", default=None)
)


def current() -> Optional["SpanRecorder"]:
    """The recorder of the batch this context runs in, or None."""
    return _CURRENT.get()


class _Span:
    __slots__ = ("_rec", "_name", "_nbytes", "_ann", "_t0", "seconds")

    def __init__(self, rec: "SpanRecorder", name: str,
                 nbytes: Optional[int]):
        self._rec = rec
        self._name = name
        self._nbytes = nbytes
        self._ann = rec._annotation(name)
        self.seconds = 0.0

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._rec._add(self._name, self.seconds, self._nbytes)
        return False


class SpanRecorder:
    """Seconds, counts and bytes of named spans, kept per thread."""

    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        # thread id -> name -> [seconds, count, {field: count} or None];
        # each thread writes only its own dict
        self._per_thread: Dict[int, Dict[str, List]] = {}

    def span(self, name: str, nbytes: Optional[int] = None) -> _Span:
        """A context manager timing ``name``; its ``seconds`` hold the
        duration once it has exited."""
        return _Span(self, name, nbytes)

    def _row(self, name: str) -> List:
        tid = threading.get_ident()
        tot = self._per_thread.get(tid)
        if tot is None:
            tot = self._per_thread.setdefault(tid, {})
        row = tot.get(name)
        if row is None:
            row = tot[name] = [0.0, 0, None]
        return row

    def _add(self, name: str, seconds: float, nbytes: Optional[int]) -> None:
        row = self._row(name)
        row[0] += seconds
        row[1] += 1
        if nbytes is not None:
            self._bump(row, "bytes", nbytes)

    def tally(self, name: str, **fields: int) -> None:
        """Add counts to fields of span ``name``'s entry besides its
        seconds and count (each field declared in
        ``repro.search.schema.SPAN_FIELDS``)."""
        row = self._row(name)
        for field, value in fields.items():
            self._bump(row, field, value)

    @staticmethod
    def _bump(row: List, field: str, value: int) -> None:
        if row[2] is None:
            row[2] = {}
        row[2][field] = row[2].get(field, 0) + int(value)

    @contextlib.contextmanager
    def active(self) -> Iterator["SpanRecorder"]:
        """Make this the recorder :func:`current` returns, for the block."""
        token = _CURRENT.set(self)
        try:
            yield self
        finally:
            _CURRENT.reset(token)

    def totals(self) -> Dict[str, Dict[str, float]]:
        """Every thread's totals, merged by name."""
        out: Dict[str, Dict[str, float]] = {}
        for tot in list(self._per_thread.values()):
            for name, (s, n, fields) in tot.items():
                row = out.setdefault(name, {"s": 0.0, "n": 0})
                row["s"] += s
                row["n"] += n
                for field, value in (fields or {}).items():
                    row[field] = row.get(field, 0) + value
        return out


def decode_span(nbytes: int):
    """The ``index.decode`` span of the current batch over ``nbytes``
    encoded bytes, or a no-op outside a batch."""
    rec = _CURRENT.get()
    if rec is None:
        return contextlib.nullcontext()
    return rec.span("index.decode", nbytes)


def note_parallel_decode(nbytes: int) -> None:
    """Count one byte-parallel posting decode of ``nbytes`` encoded bytes
    into the current batch's ``index.decode`` entry (``parallel``,
    ``parallel_bytes``); a no-op outside a batch."""
    rec = _CURRENT.get()
    if rec is not None:
        rec.tally("index.decode", parallel=1, parallel_bytes=nbytes)
